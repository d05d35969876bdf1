"""readback_p99_ms.scalar: the store's readback (executor submit to the
host arrays in hand: a thread's wait, the device finishing, the
transfer, the unpack; runtime/store.py) at its 99th percentile over the
window, from the program's stage histogram (OP_STATS
``stages.store_readback``), reset with the native stages at the window's
start. Its buckets are 25% wide: a bucket edge, not a sample."""


def read(run):
    h = run.stages.get("store_readback")
    if not h or not h["count"]:
        return None
    return h["p99_s"] * 1e3
