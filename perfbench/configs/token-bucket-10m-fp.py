"""Least bytes any implementation must move through HBM for one decision
of ``token-bucket-10m-fp`` (device key directory; the key arrives as its
64-bit fingerprint and the kernel finds its slot)."""


def min_bytes_per_decision(cfg: dict, mix: dict) -> float:
    state = 4 + 4      # the key's balance (f32) and last-refill tick (i32)
    read_write = 2 * state          # read it, write it back
    directory = 8      # the key's directory cell (u32[2] fingerprint), read
    operand = 8 + 1    # the key's fingerprint (u64) + count (u8)
    verdict = 1 / 8    # one grant bit out
    remaining = 4 if mix.get("with_remaining", mix["op"] == "acquire") else 0
    # remaining balance (f32) out, where the request asks for it
    return read_write + directory + operand + verdict + remaining
