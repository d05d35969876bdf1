"""store_host_p99_ms.scalar: one store call's store-lock sections
(directory resolve, operand packing, the jitted launch calls;
runtime/store.py) at its 99th percentile over the window, from the
program's stage histogram (OP_STATS ``stages.store_host``), reset with
the native stages at the window's start. Its buckets are 25% wide: a
bucket edge, not a sample."""


def read(run):
    h = run.stages.get("store_host")
    if not h or not h["count"]:
        return None
    return h["p99_s"] * 1e3
