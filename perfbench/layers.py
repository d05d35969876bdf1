#!/usr/bin/env python3
"""The program's own layers in one traced run: the readings the harness
does not keep yet.

    python3 perfbench/layers.py --workload tb10m.bulk-uniform --seed 7 --seconds 20

Runs the cell once exactly as ``run.py --trace 1`` does (same set-up,
window, check and result line, printed last) and prints before it one
``layers`` line with what that run leaves out:

- ``end_to_end``: the window's end-to-end numbers (a traced run's line
  holds only per-layer metrics);
- ``stages``: every stage histogram of the native front end over the
  window (``NativeFrontend.stage_histograms``: the C side's
  ``native_queue`` / ``native_exec`` and the host parts ``fe_handoff``,
  ``store_host``, ``store_readback``), each with p50, p99, mean and
  count; ``exec_covered`` is the three parts' means over
  ``native_exec``'s;
- ``program_spans_s`` and ``span_counts``: the union inside the window
  of each ``drl/`` span the program annotates (docs/OPERATIONS.md §5),
  and how many started in it; ``resolves_per_call`` is the
  ``drl/store.resolve`` count over the ``store_host`` count (one per
  store call); ``readback_to_complete``: from a readback's end to the
  reply's start (calls paired by order: read it where they do not
  overlap);
- ``idle_by_label`` and ``idle_gaps``: device 0's idle time split by
  the program spans open on any host thread at each gap's midpoint,
  names joined by ``+``; a gap with none open keeps ``trace.py``'s
  label (``perfbench.store`` or ``no store call``).

The functions take ``trace.py``'s normalized planes. Without a TPU it
exits 2, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness  # noqa: E402
import spec  # noqa: E402
import trace as tracemod  # noqa: E402

#: Prefix of the program's own span names.
PROGRAM = "drl/"


def load(trace_dir: str) -> list[dict]:
    """``trace.load``, keeping the program's ``drl/`` host spans beside
    the benchmark's ``perfbench.`` ones."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for pl in pd.planes:
        dev = bool(tracemod._DEVICE.match(pl.name))
        lines = []
        for ln in pl.lines:
            ev = [[e.name, e.start_ns, e.duration_ns] for e in ln.events
                  if dev or e.name.startswith(("perfbench.", PROGRAM))]
            if ev:
                lines.append({"name": ln.name, "events": ev})
        out.append({"name": pl.name, "lines": lines})
    return out


def program_spans(planes: list[dict]) -> dict[str, list]:
    """Each ``drl/`` span name's events clipped to the window, over all
    host threads: ``{name: [(name, start, end), ...]}``."""
    w0, w1 = tracemod.window(planes)
    out: dict[str, list] = {}
    for pl in planes:
        if tracemod._DEVICE.match(pl["name"]):
            continue
        for ln in pl["lines"]:
            for ev in tracemod.clip([e for e in ln["events"]
                                     if e[0].startswith(PROGRAM)], w0, w1):
                out.setdefault(ev[0], []).append(ev)
    return out


def program_spans_s(planes: list[dict]) -> dict[str, float]:
    """Seconds of the window in which each ``drl/`` span was open on
    some host thread."""
    return {name: tracemod.busy_ns(ev) * 1e-9
            for name, ev in sorted(program_spans(planes).items())}


def span_counts(planes: list[dict]) -> dict[str, int]:
    """How many of each ``drl/`` span started inside the window."""
    w0, w1 = tracemod.window(planes)
    out: dict[str, int] = {}
    for pl in planes:
        if tracemod._DEVICE.match(pl["name"]):
            continue
        for ln in pl["lines"]:
            for name, start, _ in ln["events"]:
                if name.startswith(PROGRAM) and w0 <= start < w1:
                    out[name] = out.get(name, 0) + 1
    return dict(sorted(out.items()))


class _Open:
    """Which of a set of named intervals are open at an instant."""

    def __init__(self, spans: dict[str, list]) -> None:
        self._by_name = {}
        for name, ev in spans.items():
            merged = tracemod.union(ev)
            self._by_name[name] = ([a for a, _ in merged], merged)

    def at(self, t: float) -> list[str]:
        out = []
        for name, (starts, merged) in self._by_name.items():
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and merged[i][1] >= t:
                out.append(name)
        return sorted(out)


def idle_gaps(planes: list[dict], top: int = 10) -> dict:
    """Device 0's idle gaps inside the window, each labelled by the
    ``drl/`` spans open at its midpoint (``+``-joined), else by
    ``trace.py``'s store-span label: the ``top`` longest, and the idle
    seconds per label."""
    w0, w1 = tracemod.window(planes)
    devs = tracemod.devices(planes)
    if not devs:
        return {"idle_gaps": [], "idle_by_label": {}}
    program = _Open(program_spans(planes))
    store = _Open({tracemod.STORE_SPAN: tracemod.host_spans(
        planes, tracemod.STORE_SPAN, w0, w1)})
    busy = tracemod.union(tracemod.clip(tracemod.device_events(devs[0]),
                                        w0, w1))
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = []
    by_label: dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        names = program.at(mid)
        label = ("+".join(names) if names else
                 tracemod.STORE_SPAN if store.at(mid) else "no store call")
        gaps.append([label, (b - a) * 1e-9])
        by_label[label] = by_label.get(label, 0.0) + (b - a) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return {"idle_gaps": gaps[:top],
            "idle_by_label": dict(sorted(by_label.items(),
                                         key=lambda kv: -kv[1]))}


def stage_readings(hists: dict) -> dict:
    """p50, p99, mean, sum and count of each stage histogram (bucket
    edges for the quantiles, 25% wide), and how much of
    ``native_exec``'s mean its three host parts' means cover."""
    out = {name: {"p50_s": h.p50, "p99_s": h.p99, "sum_s": h.sum_s,
                  "count": h.total,
                  "mean_s": h.sum_s / h.total if h.total else None}
           for name, h in hists.items()}
    parts = [out.get(n, {}).get("mean_s")
             for n in ("fe_handoff", "store_host", "store_readback")]
    exec_mean = out.get("native_exec", {}).get("mean_s")
    if exec_mean and all(p is not None for p in parts):
        out["exec_covered"] = sum(parts) / exec_mean
    return out


def readback_to_complete(planes: list[dict]) -> "dict | None":
    """From each ``drl/fe.complete``'s start back to the latest
    ``drl/store.readback`` end before it: the loop's resumption after
    the readback and the handler's work before the reply. Pairs the
    right call only where store calls do not overlap (one in flight, as
    in an open-loop cell below its knee)."""
    spans = program_spans(planes)
    ends = sorted(b for _, _, b in spans.get("drl/store.readback", []))
    gaps = []
    for _, a, _ in spans.get("drl/fe.complete", []):
        i = bisect.bisect_right(ends, a) - 1
        if i >= 0:
            gaps.append((a - ends[i]) * 1e-9)
    if not gaps:
        return None
    gaps.sort()
    return {"mean_s": sum(gaps) / len(gaps), "p50_s": gaps[len(gaps) // 2],
            "count": len(gaps)}


def reduce(planes: list[dict], stages: dict, top: int = 10) -> dict:
    """The ``layers`` readings of one traced window."""
    counts = span_counts(planes)
    calls = stages.get("store_host", {}).get("count")
    out = {"program_spans_s": program_spans_s(planes),
           "span_counts": counts,
           "resolves_per_call": (counts.get("drl/store.resolve", 0) / calls
                                 if calls else None),
           "readback_to_complete": readback_to_complete(planes)}
    out.update(idle_gaps(planes, top))
    return out


@contextlib.contextmanager
def _wider(kept: dict):
    """Within the block, the harness's window also keeps every stage
    reading, its trace keeps the ``drl/`` spans, and its end-to-end
    numbers and trace planes land in ``kept``. ``trace.reduce`` reads
    only its own span names, so its results are unchanged."""
    window_cls, load_fn = harness.Window, tracemod.load
    reduce_fn, e2e_fn = tracemod.reduce, harness.stats.end_to_end

    class Window(window_cls):
        def end(self) -> None:
            super().end()
            kept["stages"] = stage_readings(
                self.srv._native.stage_histograms() or {})

    def keep_reduce(planes, top=10):
        kept["planes"] = planes
        return reduce_fn(planes, top)

    def keep_e2e(*args):
        e2e = e2e_fn(*args)
        kept["end_to_end"] = {k: v for k, v in e2e.items()
                              if not k.startswith("_")}
        return e2e

    harness.Window, tracemod.load = Window, load
    tracemod.reduce, harness.stats.end_to_end = keep_reduce, keep_e2e
    try:
        yield
    finally:
        harness.Window, tracemod.load = window_cls, load_fn
        tracemod.reduce, harness.stats.end_to_end = reduce_fn, e2e_fn


def run(cell: spec.Cell, seed: int, seconds: float, *,
        require_tpu: bool = True) -> tuple[dict, dict]:
    """One traced run of ``cell``: ``(layers, result)``, ``result`` being
    what ``run.py --trace 1`` prints."""
    kept: dict = {}
    with _wider(kept):
        result = harness.run(cell, seed, seconds, True,
                             require_tpu=require_tpu)
    stages = kept["stages"]
    layers = {"workload": cell.name, "seed": seed,
              "end_to_end": kept["end_to_end"], "stages": stages}
    layers.update(reduce(kept["planes"], stages))
    return layers, result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cell = spec.Cell(spec.load(), args.workload)
    try:
        layers, result = run(cell, args.seed, args.seconds)
    except harness.NoChip as exc:
        print(f"layers: {exc}", file=sys.stderr)
        return 2
    print(json.dumps({"layers": layers}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
