"""Window driver: whole fleet episodes back to back.

Each episode is a ``ClusterSimulator`` built inside the window from its
episode seed (a planner pays that cost too) and run to its end through
``ClusterSimulator.run``.  Every run does the same work: the traffic
names a fixed set of episode seeds, and the window runs whole cycles
through that set, in an order drawn from the run's seed, until its
seconds are spent.  Episodes of different seeds differ in their events
by some 5 %, so a window of a few episodes drawn from the run's seed
would measure the seed as much as the program.  A traced window, whose
metrics are rates and shares, ends at the first whole episode past its
``trace_seconds``.  Benchmark-side spans time
every ``Policy.decide`` call; a hook on the decide kernel's entry
counts the rows it scores and keeps every batch with its answer for the
check.

The check holds the window's output to the configuration's reference:
the kernel's destinations on every batch of the window against
Algorithm 1 in float64, and every episode of the window against the
guarantees the reference audits from its own copy of the job stream
(jobs, completion, energy balance, migrations counted).  The first
episode is also run again on the program's float64 numpy decide path
and its digits compared: that replay shares the event loop and the
accounting with the window, so it checks the decide backend and not the
simulator.

Traffic parameters: ``policy`` (registered policy name),
``episode_seeds`` (the set of episodes a cycle runs), ``trace_seconds``
(length of the traced window), ``limits``.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from harness import counts, spec
from harness.probe import Probe
from harness.runner import Check, Context

SCORE_PARAM_KEYS = ("alpha", "gamma", "beta", "queue_penalty_s",
                    "min_benefit_s", "eps", "forecast_sigma_s")


SIM_KEYS = ("n_sites", "slots_per_site", "wan_gbps", "n_jobs", "days",
            "frac_a", "frac_b", "mean_compute_h", "orch_dt_s")


def _overrides(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The program's settings for one episode, all from the
    configuration file."""
    a1 = cfg["algorithm1"]
    out = {k: cfg[k] for k in SIM_KEYS}
    out.update({k: tuple(cfg[k]) for k in
                ("size_a_gb", "size_b_gb", "size_c_gb", "arrival_skew")})
    out.update(p_node_kw=a1["p_node_kw"], p_sys_kw=a1["p_sys_kw"],
               t_load_s=a1["t_load_s"], t_downtime_s=a1["t_downtime_s"],
               seed=seed)
    return out


def _job_columns(r) -> Dict[str, np.ndarray]:
    """The per-job columns of an episode's result, for the audit."""
    cls = {"A": 0, "B": 1, "C": 2}
    cols = {k: np.array([getattr(j, k) for j in r.jobs]) for k in (
        "jid", "arrival_s", "compute_s", "ckpt_bytes", "progress_s",
        "done_s", "migrations")}
    cols["cls"] = np.array([cls.get(j.size_class, -1) for j in r.jobs])
    cols["home"] = np.array([j.home_site for j in r.jobs])
    return cols


def _totals(r) -> Dict[str, float]:
    return {"grid_kwh": r.grid_kwh, "renewable_kwh": r.renewable_kwh,
            "migration_kwh": r.migration_kwh, "migrations": r.migrations}


def _rows(batch, b: int) -> Dict[str, np.ndarray]:
    """Cell ``b``'s unpadded columns of a kernel batch."""
    k, n = batch.n_jobs[b], batch.n_sites[b]
    return {"sizes": batch.sizes[b, :k], "t_loads": batch.t_loads[b, :k],
            "rem": batch.rem[b, :k], "cur_green": batch.cur_green[b, :k],
            "load_src": batch.load_src[b, :k], "s_i": batch.s_i[b, :k],
            "bw": batch.bw[b, :k, :n], "W": batch.W[b, :n],
            "bq_load": batch.bq_load[b, :n],
            "free_slots": batch.free_slots[b, :n]}


def _params(p) -> Dict[str, float]:
    return {k: float(getattr(p, k)) for k in SCORE_PARAM_KEYS}


def setup(ctx: Context) -> Dict[str, Any]:
    from repro.core import policy_kernels as pk
    from repro.core.orchestrator import make_policy
    from repro.core.scenarios import get_scenario

    cfg, traffic = ctx.config, ctx.traffic
    ref = spec.reference(ctx.cell)
    name = pk.backend()
    program = original = pk._SCORE_FNS[name]
    if ctx.variant == "control":
        # the reference in the program's place, one precision below the
        # kernel's float32
        import ml_dtypes

        def program(batch, params):  # noqa: F811
            out = np.full(batch.sizes.shape, -1, np.int64)
            for b in range(batch.sizes.shape[0]):
                out[b, :batch.n_jobs[b]] = ref.destinations(
                    _rows(batch, b), _params(params), cfg["algorithm1"],
                    ml_dtypes.bfloat16)
            return out
    inner = ctx.plant("decide_kernel", program)
    st: Dict[str, Any] = {"backend": name, "original": original, "calls": [],
                          "answers": []}

    def scored(batch, params):
        dest = inner(batch, params)
        work = [counts.decide_kernel(k, n)
                for k, n in zip(batch.n_jobs, batch.n_sites)]
        st["calls"].append(work)
        dest = np.array(dest)
        st["answers"].append((batch, params, dest))
        return dest

    # warm every padded shape an episode can reach: one cell per call,
    # job rows up to the fleet's slots (only running jobs are candidates)
    scn = get_scenario(cfg["scenario"])
    policy = make_policy(traffic["policy"],
                         **dict(scn.policy_configs.get(traffic["policy"], {})))
    params = policy._params()
    n = cfg["n_sites"]
    top = pk.pad_jobs(min(cfg["n_jobs"], n * cfg["slots_per_site"]))
    k = 8
    while k <= top:
        rows = pk.StateRows(
            sizes=np.full(k, 1e9), t_loads=np.full(k, 10.0),
            rem=np.full(k, 3600.0), cur_green=np.zeros(k),
            load_src=np.ones(k), s_i=np.zeros(k, np.int64),
            bw=np.full((k, n), 1e9), W=np.full(n, 3600.0),
            bq_load=np.zeros(n), free_slots=np.ones(n, np.int64))
        inner(pk.build_batch([rows]), params)
        k *= 2
    pk._SCORE_FNS[name] = scored
    st["scenario"] = scn.name
    return st


def _episode(ctx: Context, seed: int, probe: Probe):
    from repro.core import ClusterSimulator

    with probe.span("episode_build"):
        sim = ClusterSimulator.from_scenario(
            ctx.config["scenario"], ctx.traffic["policy"],
            overrides=_overrides(ctx.config, seed))
        sim.policy.decide = probe.timed("decide", sim.policy.decide)
    with probe.span("episode_run"):
        return sim.run()


def _digits(r) -> tuple:
    return (r.completed, r.migrations, r.grid_kwh, r.grid_gco2)


def window(ctx: Context, st: Dict[str, Any]) -> Dict[str, float]:
    seconds = ctx.seconds
    if ctx.traced:
        seconds = min(seconds, ctx.traffic["trace_seconds"])
    st["calls"].clear()
    st["answers"].clear()
    t0 = time.perf_counter()
    sim_s, ticks, ep = 0.0, 0, 0
    st["audits"] = []
    order = np.random.default_rng(ctx.subseed(1)).permutation(
        ctx.traffic["episode_seeds"])
    while time.perf_counter() - t0 < seconds:
        for seed in order:
            seed = int(seed)
            r = _episode(ctx, seed, ctx.probe)
            if ep == 0:
                st["first"] = (seed, _digits(r))
            st["audits"].append((seed, r))
            # an episode plans the configuration's whole horizon, however
            # early its last job completes
            sim_s += ctx.config["days"] * 86400.0
            ticks += r.ticks
            ep += 1
            # the per-layer metrics are rates and shares, so a traced
            # window ends at the first whole episode past its seconds
            if ctx.traced and time.perf_counter() - t0 >= seconds:
                break
    wall = time.perf_counter() - t0
    st.update(episodes=ep, sim_s=sim_s, ticks=ticks, wall=wall)
    decide_ms = np.asarray(ctx.probe.spans["decide"]) * 1e3
    ctx.info.update(decide_calls=list(st["calls"]), episodes=ep,
                    decide_s=float(decide_ms.sum() * 1e-3), wall_s=wall,
                    sim_events=ticks)
    return {"sim_s_per_s": sim_s / wall,
            "decide_ms_p95": float(np.percentile(decide_ms, 95))}


def check(ctx: Context, st: Dict[str, Any]):
    from repro.core import policy_kernels as pk

    ref = spec.reference(ctx.cell)
    consts = ctx.config["algorithm1"]
    pk._SCORE_FNS[st["backend"]] = st["original"]
    mismatched = rows = bad_batches = 0
    for batch, params, dest in st.pop("answers"):
        bad = False
        for b in range(batch.sizes.shape[0]):
            k = batch.n_jobs[b]
            want = ref.destinations(_rows(batch, b), _params(params), consts)
            miss = int((np.asarray(dest[b, :k]) != want).sum())
            mismatched += miss
            rows += k
            bad |= miss > 0
        bad_batches += bad
    # the first episode again on the float64 numpy decide path
    seed, got = st["first"]
    pk.set_backend("numpy")
    try:
        replay = _digits(_episode(ctx, seed, Probe()))
    finally:
        pk.set_backend(None)
    differ = sum(a != b for a, b in zip(got, replay))
    # every episode of the window against the reference's audit
    limits = ctx.traffic["limits"]
    audit, bad_episodes = {}, 0
    for ep_seed, r in st.pop("audits"):
        found = ref.audit(ctx.config, ep_seed, _job_columns(r), _totals(r))
        bad_episodes += any(v > limits[k] for k, v in found.items())
        for k, v in found.items():
            audit[k] = max(audit.get(k, 0.0), v)
    print(f"[bench] {st['episodes']} episode(s), {st['ticks']} events, "
          f"{len(st['calls'])} kernel calls on {st['backend']}; "
          f"{rows} job rows compared with the reference; first "
          f"episode (seed {seed}) completed/migrations/grid kWh/gCO2 "
          f"{got} on the window's path, {replay} on the numpy path",
          flush=True, file=__import__("sys").stderr)
    checks = [Check("dest_mismatch", mismatched, limits["dest_mismatch"]),
              Check("rows_checked", rows, 1, upper=False),
              Check("first_episode_digits_differ", differ,
                    limits["first_episode_digits_differ"])]
    checks += [Check(k, v, limits[k]) for k, v in audit.items()]
    return (len(st["calls"]), bad_batches + (differ > 0) + bad_episodes,
            checks)


def close(ctx: Context, st: Dict[str, Any]) -> None:
    from repro.core import policy_kernels as pk

    pk._SCORE_FNS[st["backend"]] = st["original"]
