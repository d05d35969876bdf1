"""Host time of one served decision dispatch, in two operand forms.

A bulk chunk hands the jitted scan kernel a packed operand and a ``now``
vector. This probe times, on the host clock, the dispatch of one 4,096-row
chunk (K=1) in two forms, alternated call by call:

- ``asarray``: ``jnp.asarray`` each operand, then call the kernel;
- ``numpy``: pass the numpy operands to the kernel (jit copies them up
  inside the call).

Two kernels: ``acquire_scan`` on a 2^24-slot bucket table (host key
directory) and ``fp_acquire_scan_fused`` on a 2^26-slot fingerprint table.
Each is timed idle and under load: 8 threads that keep reading back fresh
results (``device_get``) and unpacking them (``np.unpackbits``), as the
served readbacks do. The device finishes each launch before the next is
timed, so only host work is on the clock.

    python3 -m benchmarks.dispatch_probe [--iters 400] [--out FILE]

Prints one JSON line: per kernel, condition and form, the dispatch time's
p50, mean and p90 in microseconds.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributedratelimiting.redis_tpu.ops import fp_directory as F
from distributedratelimiting.redis_tpu.ops import kernels as K

W = 4096


def _stats(xs: list[float]) -> dict:
    a = np.asarray(xs) * 1e6
    return {"p50_us": float(np.percentile(a, 50)),
            "mean_us": float(a.mean()),
            "p90_us": float(np.percentile(a, 90)), "n": len(xs)}


class _Readbacks:
    """``n`` threads that each, in a loop, launch a small copy of an
    earlier result, read it back and unpack its bits."""

    def __init__(self, n: int, src) -> None:
        self.stop = threading.Event()
        bump = jax.jit(lambda x: x + 1)
        jax.block_until_ready(bump(src))

        def run():
            while not self.stop.is_set():
                host = jax.device_get(bump(src))
                np.unpackbits(np.asarray(host).view(np.uint8))

        self.threads = [threading.Thread(target=run, daemon=True)
                        for _ in range(n)]
        for t in self.threads:
            t.start()

    def close(self) -> None:
        self.stop.set()
        for t in self.threads:
            t.join()


def _probe(call, operands, iters: int, load: int) -> dict:
    """Time ``call(*ops)`` against ``call(*map(jnp.asarray, ops))``,
    alternated; ``operands(i)`` makes iteration ``i``'s numpy operands.
    ``call`` returns the result to finish before the next timing."""
    times = {"asarray": [], "numpy": []}
    # Both forms compile (and the first trace of each signature) first.
    for form in times:
        ops = operands(0)
        if form == "asarray":
            ops = [jnp.asarray(o) for o in ops]
        jax.block_until_ready(call(*ops))
    readers = _Readbacks(load, call(*operands(0))) if load else None
    try:
        for i in range(iters):
            for form in (("asarray", "numpy") if i % 2 else
                         ("numpy", "asarray")):
                ops = operands(i)
                t0 = time.perf_counter()
                if form == "asarray":
                    ops = [jnp.asarray(o) for o in ops]
                out = call(*ops)
                times[form].append(time.perf_counter() - t0)
                jax.block_until_ready(out)
    finally:
        if readers is not None:
            readers.close()
    return {form: _stats(xs) for form, xs in times.items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=400)
    ap.add_argument("--bucket-slots", type=int, default=2**24)
    ap.add_argument("--fp-slots", type=int, default=2**26)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    g = np.random.default_rng(20261019)
    cap, rate = jnp.float32(100.0), jnp.float32(10 / 1024)
    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform,
                         "device_kind": dev.device_kind},
              "rows": W, "threads": args.threads}

    state = [K.init_bucket_state(args.bucket_slots)]

    def bucket_ops(i):
        slots = g.integers(0, args.bucket_slots, (1, W), dtype=np.int32)
        return (K.pack_rows(slots, np.ones((1, W), np.uint8)),
                np.full((1,), 1000 + i, np.int32))

    def bucket_call(rows, nows):
        state[0], out = K.acquire_scan(state[0], rows, nows, cap, rate)
        return out

    fp = [F.init_fp_table(args.fp_slots), K.init_bucket_state(args.fp_slots)]

    def fp_ops(i):
        keys = g.integers(1, 2**32, (W, 2), dtype=np.uint64).astype(np.uint32)
        return (F.pack_fp12(keys, np.ones(W, np.int64)).reshape(1, W, 3),
                np.full((1,), 1000 + i, np.int32))

    def fp_call(fused, nows):
        fp[0], fp[1], out = F.fp_acquire_scan_fused(
            fp[0], fp[1], fused, nows, cap, rate)
        return out

    for name, call, ops in (("acquire_scan", bucket_call, bucket_ops),
                            ("fp_acquire_scan_fused", fp_call, fp_ops)):
        result[name] = {
            "idle": _probe(call, ops, args.iters, 0),
            "loaded": _probe(call, ops, args.iters, args.threads)}
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
