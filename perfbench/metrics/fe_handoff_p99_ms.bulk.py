"""fe_handoff_p99_ms.bulk: the pump -> loop hand-off of a bulk job (its
arrival in the front end to the first line of the loop's bulk handler,
runtime/native_frontend.py: the pump's copy, then the wait for the
loop) at its 99th percentile over the window, from the program's stage
histogram (OP_STATS ``stages.fe_handoff``), reset with the native stages
at the window's start. Its buckets are 25% wide: a bucket edge, not a
sample."""


def read(run):
    h = run.stages.get("fe_handoff")
    if not h or not h["count"]:
        return None
    return h["p99_s"] * 1e3
