"""Benchmark runner: one function per paper table/figure.
Each prints its table then a ``name,us_per_call,derived`` CSV line.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --fast     # smaller sims
  PYTHONPATH=src python -m benchmarks.run --only table6_policy
  PYTHONPATH=src python -m benchmarks.run --quick    # CI perf smoke:
      full 7-day/240-job paper-table6 sim; prints wall time + ticks/sec
      and writes BENCH_quick.latest.json next to the committed
      BENCH_quick.json baseline (see benchmarks/check_quick.py for the
      CI regression gate)
"""
from __future__ import annotations

import argparse
import heapq
import json
import os
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

QUICK_BASELINE = os.path.join(os.path.dirname(__file__), "BENCH_quick.json")
QUICK_LATEST = os.path.join(os.path.dirname(__file__), "BENCH_quick.latest.json")


def calibrate() -> float:
    """Wall seconds for a fixed python+numpy workload shaped like the sim
    hot loop (heap churn + small-array numpy).  Stored alongside ticks/sec
    so check_quick.py can normalize away machine-speed differences between
    the committed baseline and the CI runner.  Best-of-3, matching the
    best-of-N treatment the sim runs themselves get."""
    import numpy as np

    def once() -> float:
        t0 = time.perf_counter()
        rng = np.random.default_rng(0)
        x = rng.random(512)
        acc = 0.0
        for _ in range(400):
            acc += float(np.minimum(x, 0.5).sum())
            h: list = []
            for i in range(512):
                heapq.heappush(h, (float(x[i]) + i, i))
            while h:
                heapq.heappop(h)
        assert acc > 0
        return time.perf_counter() - t0

    return min(once() for _ in range(3))


#: 25-site fleet variant of forecastable-brownouts: the scale where the
#: vectorized decide path pulls away from the scalar oracle (~4x on the
#: decide wall; at 5 sites numpy dispatch ~= python-loop cost).
FLEET_OVERRIDES = dict(n_sites=25, n_jobs=1200, arrival_skew=(1.0,) * 25)

#: 100-site x 10k-job variant: the O(100) sites x O(10^4..10^5) jobs regime
#: the compiled decide path targets.  One 7-day run ticks ~8000x faster
#: than real time; decide wall is ~5x below the pre-batched (PR 4)
#: reservation-loop path.
FLEET_COMPILED_OVERRIDES = dict(n_sites=100, n_jobs=10000,
                                arrival_skew=(1.0,) * 100)

#: 1000-cell mini-sweep (2 scenarios x 1 policy x 500 seeds of tiny
#: 1-day cells): the many-small-cells regime where the cross-cell batched
#: runner amortizes per-cell python/numpy dispatch into one fused kernel
#: pass per tick round.
SWEEP_BATCHED_SPEC = dict(
    scenarios=("paper-table6", "forecastable-brownouts"),
    policies=("feasibility-aware",), seeds=tuple(range(500)),
    overrides=dict(n_jobs=6, days=1, orch_dt_s=1800.0))


#: 8-run process-pool mini-sweep (2 scenarios x 2 policies x 2 seeds of
#: 3-day, 80-job cells): the pool fan-out end to end.
MINI_SWEEP_SPEC = dict(
    scenarios=("paper-table6", "forecastable-brownouts"),
    policies=("feasibility-aware", "plan-ahead"), seeds=(0, 1),
    overrides=dict(days=3, n_jobs=80))


def quick_smoke(json_path: str = QUICK_LATEST) -> int:
    """Perf gate for the orchestration hot loop: full 7-day runs — the
    headline ``paper-table6`` scenario, the forecast-driven ``plan-ahead``
    policy on ``forecastable-brownouts`` (per-link outage calendar +
    ForecastHorizon grids every tick) at the paper's 5 sites and at the
    25-site fleet scale, the signal-aware ``receding-horizon`` planner on
    ``carbon-peaks`` (multi-window plan search + carbon accounting every
    span) and on ``price-spread`` (scenario-scoped non-zero price
    weight), the serving plane on ``train-plus-serve`` (carbon-slo
    router: request events + replica queues interleaved with training
    migrations), the fault-injection subsystem on ``chaos-monkey`` (all
    five fault classes mildly on; the fault-blind ``energy-only`` policy
    exercises the watchdog-abort -> retry -> reroute ladder and must
    still land every job), plus a mini Monte-Carlo sweep (2 scenarios x
    2 policies x 2 seeds through the process-pool engine).  Ticks/sec = processed events
    per second under the next-event engine; ``decide_s`` = cumulative
    wall time inside ``Policy.decide``."""
    from repro.core import ClusterSimulator
    from repro.core.sweep import SweepSpec, run_sweep

    print("name,us_per_call,derived")
    ok = True
    record = {"engine": None, "calib_s": round(calibrate(), 4), "policies": {}}
    for label, scenario, policy, overrides in (
        ("feasibility-aware", "paper-table6", "feasibility-aware", None),
        ("energy-only", "paper-table6", "energy-only", None),
        ("plan-ahead", "forecastable-brownouts", "plan-ahead", None),
        ("plan-ahead-fleet", "forecastable-brownouts", "plan-ahead",
         FLEET_OVERRIDES),
        ("receding-horizon", "carbon-peaks", "receding-horizon", None),
        ("receding-horizon-price", "price-spread", "receding-horizon", None),
        ("receding-horizon-battery", "battery-bridging", "receding-horizon",
         None),
        ("carbon-slo", "train-plus-serve", "feasibility-aware", None),
        ("chaos-monkey", "chaos-monkey", "energy-only", None),
        ("fleet-compiled", "forecastable-brownouts", "feasibility-aware",
         FLEET_COMPILED_OVERRIDES),
    ):
        best = None
        for _ in range(2):  # best-of-2: shave scheduler noise off the gate
            sim = ClusterSimulator.from_scenario(scenario, policy,
                                                 overrides=overrides)
            r = sim.run()
            if best is None or r.wall_time_s < best.wall_time_s:
                best = r
        r = best
        span_s = sim.cfg.days * 86400.0
        record["engine"] = r.engine
        print(f"[quick] {label}@{scenario}: {r.wall_time_s:.2f}s wall for "
              f"{r.ticks} ticks ({r.ticks_per_sec:.0f} ticks/sec, "
              f"decide {r.decide_s:.2f}s) | grid={r.grid_kwh:.1f} kWh "
              f"gco2={r.grid_gco2:.0f} g cost=${r.grid_cost:.2f} "
              f"renew_frac={r.renewable_fraction:.2f} migrations={r.migrations} "
              f"completed={r.completed} rejected={r.rejected_actions}")
        print(f"quick_{label},{r.wall_time_s * 1e6:.0f},"
              f"{r.ticks_per_sec:.0f} ticks/sec")
        record["policies"][label] = {
            "scenario": scenario,
            "wall_s": round(r.wall_time_s, 4),
            "ticks": r.ticks,
            "ticks_per_sec": round(r.ticks_per_sec, 1),
            "decide_s": round(r.decide_s, 4),
            "decide_first_s": round(r.decide_first_s, 4),
            "grid_kwh": round(r.grid_kwh, 1),
            "renewable_kwh": round(r.renewable_kwh, 1),
            "grid_gco2": round(r.grid_gco2, 1),
            "grid_cost": round(r.grid_cost, 2),
            "migrations": r.migrations,
            "completed": r.completed,
            "rejected_actions": r.rejected_actions,
        }
        if label == "fleet-compiled":
            # the acceptance regime: a 100-site fleet week must tick far
            # faster than real time, with XLA compile (first decide tick)
            # reported apart from the steady-state decide wall
            rt = span_s / max(r.wall_time_s, 1e-9)
            print(f"[quick]   fleet: {rt:.0f}x real time "
                  f"(decide {r.decide_s:.2f}s steady + "
                  f"{r.decide_first_s:.2f}s first-tick)")
            record["policies"][label]["realtime_factor"] = round(rt, 1)
        if r.battery_charge_kwh > 0.0 or r.sellback_kwh > 0.0:
            # the prosumer microgrid row: storage cycling + export revenue
            # from the PowerLedger, alongside the usual carbon digits
            print(f"[quick]   battery: charge={r.battery_charge_kwh:.1f} kWh "
                  f"discharge={r.battery_discharge_kwh:.1f} kWh "
                  f"cycles={r.battery_cycles:.2f} "
                  f"sellback={r.sellback_kwh:.1f} kWh "
                  f"(${r.sellback_usd:.2f}) "
                  f"dr_compliance={r.dr_compliance:.3f}")
            record["policies"][label].update({
                "battery_charge_kwh": round(r.battery_charge_kwh, 1),
                "battery_discharge_kwh": round(r.battery_discharge_kwh, 1),
                "battery_cycles": round(r.battery_cycles, 3),
                "sellback_kwh": round(r.sellback_kwh, 1),
                "sellback_usd": round(r.sellback_usd, 2),
                "dr_compliance": round(r.dr_compliance, 4),
            })
        if r.requests_arrived > 0:
            print(f"[quick]   serving: served={r.requests_served}"
                  f"/{r.requests_arrived} dropped={r.requests_dropped} "
                  f"slo_violations={r.slo_violations} "
                  f"p95={r.latency_p95_s:.2f}s "
                  f"request_gco2={r.request_gco2:.1f} g")
            record["policies"][label].update({
                "requests_arrived": r.requests_arrived,
                "requests_served": r.requests_served,
                "requests_dropped": r.requests_dropped,
                "slo_violations": r.slo_violations,
                "request_gco2": round(r.request_gco2, 1),
                "latency_p95_s": round(r.latency_p95_s, 3),
            })
            ok &= r.requests_served > 0
        if r.site_outages > 0 or r.watchdog_aborts > 0:
            # the fault-injection row: recovery-ladder telemetry (the
            # fault-blind policy walks watchdog aborts -> retries ->
            # reroutes yet still lands every job)
            print(f"[quick]   faults: outages={r.site_outages} "
                  f"mttr={r.mttr_s:.1f}s retries={r.retries} "
                  f"reroutes={r.reroutes} "
                  f"watchdog_aborts={r.watchdog_aborts} "
                  f"failed_migrations={r.failed_migrations}")
            record["policies"][label].update({
                "site_outages": r.site_outages,
                "mttr_s": round(r.mttr_s, 1),
                "retries": r.retries,
                "reroutes": r.reroutes,
                "watchdog_aborts": r.watchdog_aborts,
                "failed_migrations": r.failed_migrations,
            })
        ok &= r.completed == len(r.jobs)
    # serving fast path: the chunked engine against its per-event parity
    # oracle on the dedicated ~1.1M-request serving week.  Interleaved
    # best-of-2 per engine on the same machine — the gated quantity is
    # the requests/sec RATIO, so machine speed cancels out of the floor;
    # summaries minus timing must agree exactly (the fast path's
    # determinism contract).
    from repro.core.sweep import TIMING_KEYS

    ch_w = ev_w = None
    ch_r = ev_r = None
    for _ in range(2):
        for eng in ("chunked", "event"):
            sim = ClusterSimulator.from_scenario(
                "inference-heavy", "static",
                overrides=dict(serving_engine=eng))
            r = sim.run()
            if eng == "chunked":
                if ch_w is None or r.wall_time_s < ch_w:
                    ch_w, ch_r = r.wall_time_s, r
            elif ev_w is None or r.wall_time_s < ev_w:
                ev_w, ev_r = r.wall_time_s, r

    def _strip(d):
        # json round-trip so NaN columns (mean_jct_h on a zero-job
        # scenario) compare equal instead of poisoning dict equality
        return json.dumps({k: v for k, v in d.items()
                           if k not in TIMING_KEYS}, sort_keys=True)

    same_serving = _strip(ch_r.summary()) == _strip(ev_r.summary())
    req_s = ch_r.requests_arrived / max(ch_w, 1e-9)
    sp = ev_w / max(ch_w, 1e-9)
    print(f"[quick] inference-heavy: chunked {ch_w:.2f}s vs per-event "
          f"{ev_w:.2f}s for {ch_r.requests_arrived} requests "
          f"({req_s:,.0f} req/s, {sp:.2f}x), identical={same_serving} | "
          f"served={ch_r.requests_served} dropped={ch_r.requests_dropped} "
          f"slo_violations={ch_r.slo_violations} "
          f"p95={ch_r.latency_p95_s:.2f}s")
    print(f"quick_inference_heavy,{ch_w * 1e6:.0f},{sp:.2f}x")
    record["serving_fastpath"] = {
        "scenario": "inference-heavy",
        "requests_arrived": ch_r.requests_arrived,
        "requests_served": ch_r.requests_served,
        "requests_dropped": ch_r.requests_dropped,
        "slo_violations": ch_r.slo_violations,
        "latency_p95_s": round(ch_r.latency_p95_s, 3),
        "request_gco2": round(ch_r.request_gco2, 1),
        "chunked_wall_s": round(ch_w, 4),
        "event_wall_s": round(ev_w, 4),
        "req_per_s": round(req_s, 1),
        "speedup": round(sp, 2),
        "identical": same_serving,
    }
    ok &= same_serving and ch_r.requests_served > 0
    # mini-sweep: exercises the process-pool fan-out end to end in CI
    sw = run_sweep(SweepSpec(**MINI_SWEEP_SPEC), workers=2,
                   keep_results=False)
    completed = sum(r.summary["completed"] for r in sw.runs)
    # the gated quantity is the summed in-simulator wall, not the pool
    # wall: process spawn/import overhead tracks runner provisioning, not
    # the code under test
    sim_wall = sum(r.summary["wall_s"] for r in sw.runs)
    print(f"[quick] mini-sweep: {len(sw.runs)} runs "
          f"(2 scen x 2 pol x 2 seeds) in {sw.wall_s:.2f}s pool wall "
          f"({sw.workers} workers, {sim_wall:.2f}s summed sim wall), "
          f"completed={completed}")
    print(f"quick_sweep,{sw.wall_s * 1e6:.0f},{len(sw.runs)} runs")
    record["sweep"] = {
        "runs": len(sw.runs), "workers": sw.workers,
        "wall_s": round(sw.wall_s, 4), "sim_wall_s": round(sim_wall, 4),
        "completed": completed,
    }
    ok &= completed == 2 * 2 * 2 * 80
    # 1000-cell batched-vs-pool sweep: the cross-cell fused decide path
    # against the process-pool engine on identical cells.  The gated
    # quantity is the summed in-simulator decide wall (steady + first
    # tick) — pool spawn/IPC overhead tracks runner provisioning, not
    # the kernels under test.  Summaries minus TIMING_KEYS must agree
    # exactly (the batched runner's determinism contract).
    from repro.core.sweep import run_cells, run_cells_batched

    bspec = SweepSpec(**SWEEP_BATCHED_SPEC)
    dec = lambda sw: sum(  # noqa: E731
        r.summary["decide_s"] + r.summary["decide_first_s"]
        for r in sw.runs)
    pool_dec = batch_dec = pool = batched = None
    for _ in range(2):  # best-of-2 per engine, like the policy rows
        p = run_cells(bspec.cells(keep_results=False), workers=2,
                      keep_results=False)
        b = run_cells_batched(bspec.cells(keep_results=False),
                              keep_results=False)
        if pool_dec is None or dec(p) < pool_dec:
            pool, pool_dec = p, dec(p)
        if batch_dec is None or dec(b) < batch_dec:
            batched, batch_dec = b, dec(b)
    ratio = pool_dec / max(batch_dec, 1e-9)
    same = (pool.deterministic_summaries()
            == batched.deterministic_summaries())
    bdone = sum(r.summary["completed"] for r in batched.runs)
    print(f"[quick] sweep-batched: {len(batched.runs)} runs, decide "
          f"{pool_dec:.2f}s pool vs {batch_dec:.2f}s batched "
          f"({ratio:.2f}x), deterministic={same}, completed={bdone}")
    print(f"quick_sweep_batched,{batch_dec * 1e6:.0f},{ratio:.2f}x")
    record["sweep_batched"] = {
        "runs": len(batched.runs),
        "pool_decide_s": round(pool_dec, 4),
        "batched_decide_s": round(batch_dec, 4),
        "speedup": round(ratio, 2),
        "deterministic": same,
        "completed": bdone,
    }
    ok &= same
    with open(json_path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(f"[quick] wrote {json_path} (calib {record['calib_s']}s)")
    return 0 if ok else 1


def sweep_table(workers=None) -> None:
    """``--sweep``: the Monte-Carlo evaluation the single-seed tables
    cannot give — 5 scenarios x 3 policies x 8 seeds, full 7-day runs,
    fanned out over the process pool; prints mean +/- 95% CI per
    metric."""
    from repro.core.sweep import SweepSpec, run_sweep

    spec = SweepSpec(
        scenarios=("paper-table6", "flaky-wan", "solar-heavy",
                   "hub-spoke-wan", "forecastable-brownouts"),
        policies=("energy-only", "feasibility-aware", "plan-ahead"),
        seeds=tuple(range(8)))
    sw = run_sweep(spec, workers=workers, keep_results=False)
    print(sw.table())
    print(f"[sweep] {len(sw.runs)} runs ({sw.workers} workers) "
          f"in {sw.wall_s:.1f}s")
    print(f"sweep,{sw.wall_s * 1e6:.0f},{len(sw.runs)} runs")


def profile_run(scenario: str, policy: str, out_csv: str) -> None:
    """``--profile``: cProfile one full run and emit the top-15
    cumulative-time rows as CSV — so the next perf PR starts from data,
    not guesses."""
    import cProfile
    import pstats

    from repro.core import ClusterSimulator

    sim = ClusterSimulator.from_scenario(scenario, policy)
    srv_tm = (sim.serving.enable_timing()
              if sim.serving is not None else None)
    pr = cProfile.Profile()
    pr.enable()
    r = sim.run()
    pr.disable()
    print(f"[profile] {policy}@{scenario}: {r.wall_time_s:.2f}s wall "
          f"(decide {r.decide_s:.2f}s steady + {r.decide_first_s:.2f}s "
          f"first-tick — XLA compile lands in the first tick; profile "
          f"steady-state perf against decide_s), {r.ticks} ticks")
    if srv_tm is not None:
        # per-event-class serving breakdown (both planes accumulate the
        # same keys; the chunked engine books merged spans to chunk_s)
        total = sum(srv_tm.values())
        parts = " ".join(f"{k[:-2]}={v:.2f}s" for k, v in srv_tm.items())
        print(f"[profile] serving breakdown ({total:.2f}s booked): "
              f"{parts}")
    stats = pstats.Stats(pr)
    stats.sort_stats("cumulative")
    rows = []
    for func in stats.fcn_list:  # already cumulative-sorted
        cc, nc, tt, ct, _ = stats.stats[func]
        file, line, name = func
        rows.append((f"{file}:{line}({name})", nc, tt, ct))
        if len(rows) >= 15:
            break
    with open(out_csv, "w") as f:
        f.write("function,ncalls,tottime_s,cumtime_s\n")
        for fn, nc, tt, ct in rows:
            f.write(f"\"{fn}\",{nc},{tt:.4f},{ct:.4f}\n")
    print(f"[profile] top-15 cumulative rows -> {out_csv}")
    for fn, nc, tt, ct in rows:
        print(f"  {ct:8.4f}s cum  {tt:8.4f}s tot  {nc:>8}x  {fn}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller trace-driven sims")
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="perf smoke only: 7-day/240-job sim + ticks/sec")
    ap.add_argument("--quick-json", default=QUICK_LATEST,
                    help="where --quick writes its JSON record")
    ap.add_argument("--sweep", action="store_true",
                    help="Monte-Carlo sweep: 5 scenarios x 3 policies x "
                         "8 seeds over the process pool, mean±CI table")
    ap.add_argument("--sweep-workers", type=int, default=None,
                    help="process-pool size for --sweep (default: cpus)")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile one run, top-15 cumulative-time CSV")
    ap.add_argument("--profile-scenario", default="forecastable-brownouts")
    ap.add_argument("--profile-policy", default="plan-ahead")
    ap.add_argument("--profile-out",
                    default=os.path.join(os.path.dirname(__file__),
                                         "PROFILE_top15.csv"))
    args = ap.parse_args()
    enable_compile_cache()

    if args.quick:
        sys.exit(quick_smoke(args.quick_json))
    if args.sweep:
        sweep_table(args.sweep_workers)
        return
    if args.profile:
        profile_run(args.profile_scenario, args.profile_policy,
                    args.profile_out)
        return

    from benchmarks import (
        fig1_breakeven, fig2_phase, roofline, table1_hardware,
        table2_checkpoints, table3_transfer, table4_classes, table6_policy,
        table7_validation, table8_baselines,
    )

    benches = [
        ("table1_hardware", table1_hardware.run, {}),
        ("table2_checkpoints", table2_checkpoints.run, {}),
        ("table3_transfer", table3_transfer.run, {}),
        ("table4_classes", table4_classes.run, {}),
        ("fig1_breakeven", fig1_breakeven.run, {}),
        ("fig2_phase", fig2_phase.run, {}),
        ("table6_policy", table6_policy.run, {"fast": args.fast}),
        ("table7_validation", table7_validation.run, {}),
        ("table8_baselines", table8_baselines.run, {"fast": args.fast}),
        ("roofline", roofline.run, {}),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for name, fn, kw in benches:
        if args.only and name != args.only:
            continue
        print(f"\n=== {name} ===")
        try:
            fn(**kw)
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"{name},0,FAILED")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
