"""The queued device-bench debt list — repo-resident so it survives
watcher loss (ROADMAP item 4a; ISSUE 7 satellite).

These measurements are still OWED against the kernel-speed story; until
each lands, the numbers rest on CPU stand-ins (ROADMAP A1 turns them
into benchmark cells and replaces this runner):

1. ``fp_mesh_fixed`` — the r05 fp_mesh rework (total-slot provisioning,
   ``benchmarks/suite.py``) has no TPU number at all: the r05 run
   measured the 8×-underwater per-shard config, not this one.
2. ``fp_bulk_optimized`` — the optimized fp bulk path (fused operand,
   bit-plane verdicts) was reworked after the last healthy window; its
   device rate is extrapolated, never observed.
3. ``native_fe_device_sweep`` — the native front-end has NO number
   against a device-class (multi-ms flush) backing — the one serving
   regime the 2 ms p99 north star actually fears.

Running ``python -m benchmarks.recapture`` checks for a device with a
child process (so device-backed server children can take the chip
after it), then runs every debt still owed under a hang guard and appends evidence
to ``benchmarks/evidence/recapture.jsonl``. A debt leaves the list by
landing an ``ok`` row there — never by being forgotten. With no healthy
window the run exits 0 having written nothing: the debts persist and
fire on the first window a cron/watcher finds.

``--allow-cpu`` runs the same code paths on the CPU stand-in (smoke for
tests and plumbing work); CPU rows are stamped ``settles_debt: false``
and do not retire anything.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import threading
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
LEDGER = _ROOT / "benchmarks" / "evidence" / "recapture.jsonl"

__all__ = ["DEBTS", "owed", "main"]


# -- the debt sections -------------------------------------------------------

def _debt_fp_mesh_fixed(smoke: bool) -> dict:
    from benchmarks import suite

    return suite.bench_fp_mesh(smoke=smoke)


def _debt_fp_bulk_optimized(smoke: bool) -> dict:
    import asyncio

    import numpy as np

    from distributedratelimiting.redis_tpu.runtime.fp_store import (
        FingerprintBucketStore,
    )

    n = 1 << (10 if smoke else 17)
    store = FingerprintBucketStore(
        n_slots=1 << (12 if smoke else 21),
        max_batch=512 if smoke else 8192)
    rng = np.random.default_rng(3)
    pool = [f"user{i}" for i in range(20_000 if smoke else 1_000_000)]
    calls = [[pool[j] for j in rng.integers(0, len(pool), n)]
             for _ in range(4)]
    counts = [1] * n

    async def run() -> float:
        async def one_round() -> float:
            t0 = time.perf_counter()
            results = await asyncio.gather(
                *(store.acquire_many(c, counts, 1e7, 1e7,
                                     with_remaining=False)
                  for c in calls))
            return sum(len(r) for r in results) / (
                time.perf_counter() - t0)

        await one_round()  # warm: inserts + compile at exact shapes
        rate = max([await one_round() for _ in range(2)])
        await store.aclose()
        return rate

    rate = asyncio.run(run())
    return {"metric": "decisions_per_sec", "value": round(rate),
            "unit": "decisions/s", "keys_per_call": n}


def _debt_native_fe_device_sweep(smoke: bool) -> dict:
    """The native front-end against a device-backed store, via bench.py's
    existing child rig (one server process owning the device, one load
    process driving the C loadgen) — subprocesses so a wedged device op
    costs this section, not the runner. Round 8 added the BULK arm: the
    same device-backed server (tier-0 armed) driven with ACQUIRE_MANY
    frames through the native bulk lane — the native-FE p99 against a
    multi-ms-flush backing that the 2 ms north star actually fears."""
    env = os.environ.copy()
    env.pop("DRL_TPU_FORCE_CPU", None)
    if smoke:
        # CPU stand-in exercises the identical rig end to end.
        env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    out: dict = {}
    for arm, server_args, load_flag, load_args in (
        ("scalar", ["device", "native"], "--native-load-child", []),
        ("bulk", ["device", "native", "tier0"], "--bulk-load-child",
         ["hot"]),
    ):
        server = subprocess.Popen(
            [sys.executable, str(_ROOT / "bench.py"),
             "--serving-server-child", *server_args],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=str(_ROOT))
        try:
            line = server.stdout.readline()
            addr = json.loads(line)
            load = subprocess.run(
                [sys.executable, str(_ROOT / "bench.py"),
                 load_flag, addr["host"], str(addr["port"]), *load_args],
                capture_output=True, text=True, env=env, cwd=str(_ROOT),
                timeout=1200)
            if load.returncode != 0:
                raise RuntimeError(
                    f"{arm} load child failed: "
                    f"{load.stderr.strip()[-400:]}")
            out[arm] = json.loads(load.stdout.strip().splitlines()[-1])
        finally:
            try:
                server.stdin.close()
                server.wait(30)
            except Exception:
                server.kill()
    return {"metric": "depth_sweep", "sweep": out.get("scalar"),
            "bulk": out.get("bulk"), "unit": "req/s + ms"}


def _debt_llm_workload_device(smoke: bool) -> dict:
    """The LLM workload (ISSUE 10) against the DEVICE store: the fused
    two-level kernel (acquire_hierarchical_packed) deciding the Zipf ×
    log-normal tenant workload — its per-chip rows/s and tokens/s have
    only CPU stand-in numbers until this lands on real hardware."""
    from benchmarks import llm_workload
    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    n = 1 << (11 if smoke else 16)
    tenants, keys, costs, prios = llm_workload.gen_workload(9, n)
    store = DeviceBucketStore(n_slots=1 << (12 if smoke else 18),
                              max_batch=1024 if smoke else 4096)

    def one_round() -> float:
        t0 = time.perf_counter()
        store.acquire_hierarchical_many_blocking(
            tenants, keys, costs, llm_workload.TENANT_CAP,
            llm_workload.TENANT_RATE, llm_workload.CHILD_CAP,
            llm_workload.CHILD_RATE, with_remaining=False)
        return time.perf_counter() - t0

    one_round()  # warm: compile + slot inserts at exact shapes
    dt = min(one_round() for _ in range(2))
    total_tokens = int(costs.sum())
    return {"metric": "hier_rows_per_sec", "value": round(n / dt),
            "tokens_per_sec": round(total_tokens / dt),
            "unit": "rows/s + tokens/s", "rows": n}


def _debt_llm_reservations_device(smoke: bool) -> dict:
    """The estimate-reserve-settle lane (ISSUE 13) against the DEVICE
    store: every reserve is a fused hierarchical launch at the
    estimate, every settle a saturating debit (refund or overage
    collection) — the reserve+settle round-trip rate and settled
    tokens/s have only CPU stand-in numbers until this lands on real
    hardware."""
    import asyncio

    from benchmarks import llm_workload
    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    n = 1 << (9 if smoke else 13)
    tenants, keys, costs, prios = llm_workload.gen_workload(9, n)
    np = __import__("numpy")
    rng = np.random.default_rng(llm_workload._RESV_ERR_SEED)
    # The TRACKED estimate identity — must match lane_reservations
    # exactly or the device row stops being comparable to the CPU
    # stand-in it settles.
    estimates = np.maximum(
        costs * rng.lognormal(0.0, llm_workload.RESV_EST_SIGMA, n),
        1.0)
    store = DeviceBucketStore(n_slots=1 << (12 if smoke else 16),
                              max_batch=1024)

    async def one_round(prefix: str) -> float:
        t0 = time.perf_counter()
        _g, _s, _led = await llm_workload._drive_reservations(
            store, tenants, keys, costs, estimates, prios,
            llm_workload.TENANT_CAP, llm_workload.TENANT_RATE, prefix)
        return time.perf_counter() - t0

    asyncio.run(one_round("w"))  # warm: compile + slot inserts
    dt = min(asyncio.run(one_round(p)) for p in ("x", "y"))
    total_tokens = int(costs.sum())
    return {"metric": "reserve_settle_pairs_per_sec",
            "value": round(n / dt),
            "settled_tokens_per_sec": round(total_tokens / dt),
            "unit": "reserve+settle pairs/s", "rows": n}


def _debt_native_fe_shard_sweep(smoke: bool) -> dict:
    """The multi-shard front-end (round 11) against a DEVICE-class
    backing: shards ∈ {1, 2, 4, 8} SO_REUSEPORT epoll shards on one
    port, tier-0 armed, driven by the C bulk loadgen — the node-level
    rows/s curve whose CPU stand-in lives in
    evidence/native_shards_r11.jsonl and BENCH serving_native_shards.
    On a real device the residue rows meet a multi-ms flush, so the
    device arm is the one that prices the shield, not just the shards."""
    import concurrent.futures

    env = os.environ.copy()
    env.pop("DRL_TPU_FORCE_CPU", None)
    if smoke:
        env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    out: dict = {}
    for shards in (1, 2, 4, 8):
        server = subprocess.Popen(
            [sys.executable, str(_ROOT / "bench.py"),
             "--serving-server-child", "device", "native", "tier0",
             f"shards={shards}", "pin"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env, cwd=str(_ROOT))
        pool = concurrent.futures.ThreadPoolExecutor(1)
        try:
            line = pool.submit(server.stdout.readline).result(
                timeout=180.0)
            addr = json.loads(line)
            load = subprocess.run(
                [sys.executable, str(_ROOT / "bench.py"),
                 "--shard-load-child", addr["host"],
                 str(addr["port"]), str(shards)],
                capture_output=True, text=True, env=env,
                cwd=str(_ROOT), timeout=600)
            if load.returncode != 0:
                raise RuntimeError(
                    f"s{shards} load child failed: "
                    f"{load.stderr.strip()[-400:]}")
            out[f"s{shards}"] = json.loads(
                load.stdout.strip().splitlines()[-1])
        finally:
            try:
                server.stdin.close()
                server.wait(30)
            except Exception:
                server.kill()
            pool.shutdown(wait=False)
    if "s1" in out and "s4" in out:
        out["speedup_4v1"] = (out["s4"]["rows_per_s"]
                              / out["s1"]["rows_per_s"])
    return {"metric": "shard_sweep", "sweep": out,
            "unit": "rows/s per shard count"}


def _debt_native_fe_uring_sweep(smoke: bool) -> dict:
    """The io_uring data plane (round 16) against a DEVICE-class
    backing: the round-11 shard rig once per transport arm — epoll vs
    io_uring vs io_uring+SQPOLL at 1 and 4 shards — harvesting the
    server child's shutdown line (fe_uring_counts data-plane syscall
    counter + rusage CPU-seconds) so syscalls/frame and cycles/row get
    device-backed numbers instead of the CPU stand-ins in
    evidence/native_uring_r16.jsonl. On a host whose kernel lacks
    io_uring only the epoll arm runs and the probe verdict is
    recorded beside it — a fallback run never masquerades as ring
    numbers (the per-arm rows carry uring_shards/fallbacks)."""
    import concurrent.futures

    from distributedratelimiting.redis_tpu.runtime.native_frontend import (
        uring_probe,
    )

    env = os.environ.copy()
    env.pop("DRL_TPU_FORCE_CPU", None)
    if smoke:
        env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    ok, reason = uring_probe()
    arms = [("epoll", None)]
    if ok:
        arms += [("uring", "on"), ("sqpoll", "sqpoll")]
    out: dict = {"uring_available": ok, "probe": reason}
    for name, uring in arms:
        for shards in (1, 4):
            argv = [sys.executable, str(_ROOT / "bench.py"),
                    "--serving-server-child", "device", "native",
                    "tier0", f"shards={shards}", "pin"]
            if uring is not None:
                argv.append(f"uring={uring}")
            server = subprocess.Popen(
                argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True, env=env, cwd=str(_ROOT))
            pool = concurrent.futures.ThreadPoolExecutor(1)
            try:
                line = pool.submit(server.stdout.readline).result(
                    timeout=180.0)
                addr = json.loads(line)
                load = subprocess.run(
                    [sys.executable, str(_ROOT / "bench.py"),
                     "--shard-load-child", addr["host"],
                     str(addr["port"]), str(shards)],
                    capture_output=True, text=True, env=env,
                    cwd=str(_ROOT), timeout=600)
                if load.returncode != 0:
                    raise RuntimeError(
                        f"{name}_s{shards} load child failed: "
                        f"{load.stderr.strip()[-400:]}")
                res = json.loads(load.stdout.strip().splitlines()[-1])
                server.stdin.close()
                tail = pool.submit(server.stdout.readline).result(
                    timeout=60.0)
                if tail.strip():
                    res.update(json.loads(tail))
                tr = res.get("transport")
                if tr and res.get("frames_sent"):
                    res["syscalls_per_frame"] = round(
                        tr["io_syscalls"] / res["frames_sent"], 3)
                # ε-consumption annotation (round 18): the server
                # child's shutdown line carries the cumulative tier-0
                # grant tokens and the per-slice split (fe_t0_eps) —
                # fold them into the per-slice utilization proxy the
                # conservation auditor renders as
                # drl_epsilon_budget_used_ratio{source="shard"}, so
                # each transport arm prices drift beside its syscall
                # economics.
                eps = res.get("t0_eps_tokens")
                if eps and sum(eps) > 0:
                    res["t0_eps_hot_slice_share"] = round(
                        max(eps) / sum(eps), 4)
                grant = res.get("t0_grant_tokens")
                if grant:
                    res["t0_overadmit_per_grant"] = round(
                        res.get("t0_overadmit_total", 0.0) / grant, 9)
                out[f"{name}_s{shards}"] = res
            finally:
                try:
                    if not server.stdin.closed:
                        server.stdin.close()
                    server.wait(30)
                except Exception:
                    server.kill()
                pool.shutdown(wait=False)
    return {"metric": "uring_transport_sweep", "sweep": out,
            "unit": "syscalls/frame + rows/s per transport arm"}


def _debt_federation_device(smoke: bool) -> dict:
    """The WAN federation lane (ISSUE 15) against the DEVICE store:
    the region's local decisions from a leased slice are ordinary
    device-store acquires and the home's renew charges are
    ``debit_many`` launches — both rates have only CPU stand-in
    numbers (benchmarks/federation.py) until this lands on real
    hardware."""
    import asyncio

    from distributedratelimiting.redis_tpu.runtime.store import (
        DeviceBucketStore,
    )

    n = 1 << (10 if smoke else 14)
    cap, rate = 1e9, 1e6

    async def drive() -> dict:
        store = DeviceBucketStore(n_slots=1 << (12 if smoke else 15),
                                  max_batch=1024)
        led = store.federation_ledger(default_ttl_s=30.0)
        grant = await led.lease({
            "region": "bench", "lease_id": "dev:1",
            "tenant": "tenant:g", "demand": 1.0,
            "global_cap": cap, "global_rate": rate})
        slice_cap, slice_rate = grant["slice"]
        t0 = time.perf_counter()
        granted = 0
        for i in range(n):
            res = await store.acquire("tenant:g", 1, slice_cap,
                                      slice_rate)
            granted += int(res.granted)
        dt = time.perf_counter() - t0
        t1 = time.perf_counter()
        renew = await led.renew({
            "region": "bench", "lease_id": "dev:1",
            "tenant": "tenant:g", "total": float(granted),
            "demand": 1.0})
        renew_s = time.perf_counter() - t1
        await store.aclose()
        return {"metric": "federation_local_decisions",
                "decisions": n, "granted": granted,
                "decisions_per_s": round(n / dt, 1),
                "renew_charge_s": round(renew_s, 5),
                "renew_charged": renew["charged"],
                "unit": "slice-local decisions/s"}

    return asyncio.run(drive())


def _debt_storm_goodput_device(smoke: bool) -> dict:
    """The retry-storm goodput soak (ISSUE 20) — on this rung the CPU
    stand-in IS the full differential (benchmarks/storm_goodput.py over
    the in-process backing); what is owed is the device edition, where
    the doomed-work gate's p99 comes from a real multi-ms device flush
    and the per-row deny runs on the native bulk lane."""
    import asyncio

    from benchmarks import storm_goodput

    out = asyncio.run(storm_goodput.run_soak(storm_goodput.DEFAULT_SEED))
    return {"metric": "storm_goodput_ratio",
            "value": out["defended_ratio"],
            "naive_ratio": out["naive_ratio"],
            "baseline_goodput": out["baseline"]["goodput"],
            "defended_goodput": out["defended"]["goodput"],
            "routed": out["defended"]["counts"]["routed"],
            "retries_shed": out["defended"]["server"]["retries_shed"],
            "unit": "defended/baseline first-attempt goodput"}


#: Ordered debt list: name → (what is owed, runner). The NAME is the
#: ledger identity — renaming one un-retires it, deliberately.
DEBTS: "list[tuple[str, str, object]]" = [
    ("fp_mesh_fixed",
     "r05 fp_mesh total-slot provisioning has no TPU number "
     "(the r05 run measured the underwater per-shard config)",
     _debt_fp_mesh_fixed),
    ("fp_bulk_optimized",
     "optimized fp bulk (fused operand, bit-plane verdicts) device "
     "rate extrapolated, never observed",
     _debt_fp_bulk_optimized),
    ("native_fe_device_sweep",
     "native front-end has no number against a device-class "
     "(multi-ms flush) backing; round 8 added the "
     "native-bulk arm (ACQUIRE_MANY through the C lane, tier-0 armed)",
     _debt_native_fe_device_sweep),
    ("llm_workload_device",
     "the token-denominated LLM workload (ISSUE 10) has no device "
     "number: the fused hierarchical kernel's rows/s + tokens/s rest "
     "on the CPU stand-in (benchmarks/llm_workload.py)",
     _debt_llm_workload_device),
    ("native_fe_shard_sweep",
     "the multi-shard front-end (round 11) has no device number: the "
     "shards x {1,2,4,8} node-level curve rests on the CPU stand-in "
     "(evidence/native_shards_r11.jsonl); the device arm prices the "
     "residue path against a real multi-ms flush",
     _debt_native_fe_shard_sweep),
    ("llm_reservations_device",
     "the estimate-reserve-settle lane (ISSUE 13) has no device "
     "number: reserve = fused hierarchical launch, settle = "
     "saturating debit — the pair rate rests on the CPU stand-in "
     "(benchmarks/llm_workload.py reservations lane)",
     _debt_llm_reservations_device),
    ("federation_device",
     "the WAN federation lane (ISSUE 15) has no device number: the "
     "regional local-decision throughput behind a leased slice (and "
     "the home's debit_many settle lane under renew reports) rest on "
     "the CPU stand-in (benchmarks/federation.py)",
     _debt_federation_device),
    ("native_fe_uring_sweep",
     "the io_uring data plane (round 16) has no device number: the "
     "epoll/uring/sqpoll transport sweep — syscalls/frame and "
     "cycles/row against a real multi-ms flush — rests on the CPU "
     "stand-in (evidence/native_uring_r16.jsonl); round 18 annotates "
     "each arm with the tier-0 ε-consumption counters (fe_t0_eps "
     "per-slice grants, overadmit/grant ratio)",
     _debt_native_fe_uring_sweep),
    ("storm_goodput_device",
     "the retry-storm goodput differential (ISSUE 20) has no device "
     "number: the defended/naive/baseline arms run over the "
     "in-process backing — the doomed-work gate pricing (p99 sensing "
     "+ per-row deny on the native bulk lane) against a real "
     "multi-ms device flush rests on the CPU stand-in "
     "(benchmarks/storm_goodput.py)",
     _debt_storm_goodput_device),
]


# -- ledger ------------------------------------------------------------------

def _settled(ledger: pathlib.Path) -> set[str]:
    done: set[str] = set()
    if not ledger.exists():
        return done
    for line in ledger.read_text().splitlines():
        try:
            row = json.loads(line)
        except ValueError:
            continue  # a torn tail row must not hide the whole ledger
        if row.get("status") == "ok" and row.get("settles_debt"):
            done.add(row.get("debt", ""))
    return done


def owed(ledger: "pathlib.Path | None" = None) -> list[str]:
    """Debt names still lacking an evidence row — THE list a watcher
    (or a human) checks per round."""
    done = _settled(ledger or LEDGER)
    return [name for name, _why, _fn in DEBTS if name not in done]


def _append(ledger: pathlib.Path, row: dict) -> None:
    ledger.parent.mkdir(parents=True, exist_ok=True)
    with open(ledger, "a", encoding="utf-8") as f:
        f.write(json.dumps(row) + "\n")


def _budget_ledger_hash() -> "str | None":
    """Content hash of the checked-in drl-xla op-budget ledger
    (tools/drl_xla/budgets.json). Every debt row carries it so a
    settled number names the compiled-artifact shape it was measured
    under — a later kernel rework that changes gather/launch counts
    visibly orphans the old evidence instead of silently inheriting
    it (docs/OPERATIONS.md §19). ``None`` when the ledger is absent
    (a fresh checkout mid-restamp): the row still lands, unannotated."""
    try:
        from tools.drl_xla import budgets
        return budgets.ledger_hash(budgets.ledger_path(_ROOT))
    except Exception:
        return None


# -- device check in a child: this process must stay off the chip so the
# device-backed server children can take it --------------------------------

def _probe_platform(max_wait_s: float) -> "str | None":
    deadline = time.monotonic() + max_wait_s
    while True:
        child_timeout = min(60.0, max(deadline - time.monotonic(), 5.0))
        try:
            r = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(jax.devices()[0].platform)"],
                timeout=child_timeout, capture_output=True, text=True,
                env=os.environ.copy())
            if r.returncode == 0:
                return r.stdout.strip().splitlines()[-1]
            return None  # deterministic init failure: retrying won't fix
        except subprocess.TimeoutExpired:
            pass
        if time.monotonic() >= deadline:
            return None
        time.sleep(5)


def _run_guarded(fn, smoke: bool, timeout_s: float):
    box: dict = {}

    def target() -> None:
        try:
            box["v"] = fn(smoke)
        except BaseException as exc:  # noqa: BLE001 — a debt section
            box["e"] = f"{type(exc).__name__}: {exc}"  # must never kill
        # the runner: the remaining debts still deserve their window.

    th = threading.Thread(target=target, daemon=True)
    th.start()
    th.join(timeout_s)
    if th.is_alive():
        return "hung", None
    if "e" in box:
        return f"error: {box['e'][:300]}", None
    return "ok", box.get("v")


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--allow-cpu", action="store_true",
                        help="run the debt sections on the CPU stand-in "
                        "(smoke sizes; rows do not settle debts)")
    parser.add_argument("--force", action="store_true",
                        help="re-run debts that already have evidence")
    parser.add_argument("--only", default=None, metavar="NAME",
                        help="run just this debt (others stay owed "
                        "untouched — e.g. appending one section's CPU "
                        "stand-in row without burning a window on the "
                        "rest)")
    parser.add_argument("--probe-s", type=float, default=float(
        os.environ.get("BENCH_PROBE_S", "240")))
    parser.add_argument("--section-timeout-s", type=float, default=900.0)
    parser.add_argument("--ledger", default=str(LEDGER))
    args = parser.parse_args(argv)
    ledger = pathlib.Path(args.ledger)

    platform = _probe_platform(args.probe_s)
    device = platform is not None and platform != "cpu"
    if not device and not args.allow_cpu:
        print(json.dumps({"status": "no_healthy_device_window",
                          "owed": owed(ledger)}))
        return 0

    pending = owed(ledger) if not args.force else [n for n, _, _ in DEBTS]
    if args.only is not None:
        if args.only not in {n for n, _, _ in DEBTS}:
            print(json.dumps({"status": "unknown_debt",
                              "only": args.only,
                              "known": [n for n, _, _ in DEBTS]}))
            return 2
        pending = [n for n in pending if n == args.only]
    results = {}
    for name, why, fn in DEBTS:
        if args.only is not None and name != args.only:
            results[name] = "skipped_only"
            continue
        if name not in pending:
            results[name] = "already_settled"
            continue
        status, value = _run_guarded(fn, smoke=not device,
                                     timeout_s=args.section_timeout_s)
        row = {"debt": name, "why": why, "status": status,
               "platform": platform, "settles_debt": bool(device),
               "t": time.time(), "budget_ledger": _budget_ledger_hash(),
               "result": value}
        _append(ledger, row)
        results[name] = status
        print(json.dumps(row), flush=True)
    print(json.dumps({"status": "done", "platform": platform,
                      "results": results, "owed": owed(ledger)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
