import os
os.environ.setdefault("REPRO_UNROLL_SCAN", "1")  # full-cost accounting (see
# models/transformer.scan_or_unroll): XLA counts While bodies once.
"""Multi-pod dry-run (assignment §MULTI-POD DRY-RUN item 3) plus the
orchestration plan preview.

For every (architecture × assigned shape × mesh) cell:
  jax.jit(step).lower(**ShapeDtypeStructs).compile()
must succeed on the 16×16 single-pod mesh AND the 2×16×16 multi-pod mesh.
We record memory_analysis() (fits-in-HBM proof), cost_analysis() (FLOPs /
bytes for §Roofline) and the collective bytes parsed from the compiled HLO
(all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute)
into a JSON artifact per cell that benchmarks/roofline.py consumes.

``--plan`` is the *orchestration* dry-run: it materializes a registered
scenario at a chosen sim-time, builds the same ClusterState snapshot the
simulator hands to policies (one shared constructor,
``repro.core.state.ClusterState.build``) and prints the typed actions a
policy would emit — a what-would-happen preview without running the sim.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
  PYTHONPATH=src python -m repro.launch.dryrun --plan --scenario flaky-wan \
      --policy feasibility-aware --at-hour 36
"""
import argparse
import json
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, ASSIGNED, SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.parallel.sharding import (
    AxisRules, DEFAULT_RULES, force_mesh_axes, logical_spec, param_pspecs, use_rules,
)
from repro.train.train_step import TrainStepConfig, make_train_step

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmarks", "artifacts")

#: host devices the mesh lowering asks XLA's CPU backend for: the
#: 2x16x16 multi-pod production mesh
HOST_DEVICES = 512

# TPU v5e constants (assignment §ROOFLINE)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s/link

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes of every collective in the compiled HLO, keyed by op
    kind (output-shape bytes — bytes received per device)."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        tuple_types, single_type, kind = m.group(1), m.group(2), m.group(3)
        type_str = tuple_types if tuple_types is not None else single_type
        # skip the -done ops (shapes already counted at -start)
        pre = hlo_text[max(0, m.start() - 160): m.start()]
        if "-done" in hlo_text[m.start(): m.end()]:
            continue
        out[kind] = out.get(kind, 0) + _shape_bytes(type_str or "")
    return out


def _pspec_tree(logical_tree, mesh):
    """Convert a logical-axis-name pspec tree to PartitionSpecs."""
    def is_leaf(x):
        return isinstance(x, tuple) and (not x or not isinstance(x[0], (tuple, dict)))

    def conv(names):
        return logical_spec(*names)

    with force_mesh_axes(tuple(mesh.axis_names)):
        return jax.tree.map(conv, logical_tree, is_leaf=is_leaf)


def _shardings(tree_pspecs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _compile_once(
    cfg,
    shape_name: str,
    mesh,
    rules: AxisRules,
    *,
    remat_policy: str,
    grad_compress: bool,
    unroll: bool,
):
    """Lower+compile one step function for `cfg` on `mesh`; returns
    (flops, bytes, collectives dict, mem, compiled)."""
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    prev = os.environ.get("REPRO_UNROLL_SCAN")
    os.environ["REPRO_UNROLL_SCAN"] = "1" if unroll else "0"
    try:
        with use_rules(rules), force_mesh_axes(tuple(mesh.axis_names)):
            params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            p_shard = _shardings(param_pspecs(params_sds, rules, mesh), mesh)
            batch_sds, batch_logical = model.input_specs(shape_name)
            b_shard = _shardings(_pspec_tree(batch_logical, mesh), mesh)

            if shape.kind == "train":
                opt_sds = jax.eval_shape(init_opt_state, params_sds)
                o_shard = _shardings(param_pspecs(opt_sds, rules, mesh), mesh)
                step_cfg = TrainStepConfig(
                    remat_policy=remat_policy, grad_compress=grad_compress
                )
                fn = make_train_step(model, step_cfg)
                jfn = jax.jit(fn, in_shardings=(p_shard, o_shard, b_shard),
                              donate_argnums=(0, 1))
                args = (params_sds, opt_sds, batch_sds)
            elif shape.kind == "prefill":
                def fwd(params, batch):
                    logits, aux = model.forward(params, batch, remat_policy=remat_policy)
                    return logits

                jfn = jax.jit(fwd, in_shardings=(p_shard, b_shard))
                args = (params_sds, batch_sds)
            else:  # decode
                long = shape_name == "long_500k"
                cache_sds = batch_sds.pop("cache")
                cache_shard = b_shard.pop("cache")

                def decode(params, cache, rest):
                    return model.decode_step(params, cache, dict(rest), long_context=long)

                jfn = jax.jit(decode, in_shardings=(p_shard, cache_shard, b_shard),
                              donate_argnums=(1,))
                args = (params_sds, cache_sds, batch_sds)

            with mesh:
                lowered = jfn.lower(*args)
                compiled = lowered.compile()
                mem = compiled.memory_analysis()
                cost = compiled.cost_analysis()
    finally:
        if prev is None:
            os.environ.pop("REPRO_UNROLL_SCAN", None)
        else:
            os.environ["REPRO_UNROLL_SCAN"] = prev
    coll = collective_bytes(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    return flops, bytes_accessed, coll, mem, compiled


def force_host_devices() -> None:
    """Ask XLA's CPU backend for :data:`HOST_DEVICES` devices, keeping
    every other flag the caller put in ``XLA_FLAGS`` (and a device count
    the caller chose).  Takes effect only if JAX has not initialized its
    backends yet in this process."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={HOST_DEVICES}"
        ).strip()


def _reduced_depth(cfg, k: int):
    """Same arch with k layer-groups (pattern preserved)."""
    import dataclasses as _dc

    kw = {"num_layers": len(cfg.block_pattern) * k}
    if cfg.encoder_layers:
        kw["encoder_layers"] = k
    return _dc.replace(cfg, name=f"{cfg.name}@g{k}", **kw)


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    rules: Optional[AxisRules] = None,
    remat_policy: str = "full",
    grad_compress: bool = False,
    save_artifact: bool = True,
    artifact_dir: Optional[str] = None,
    tag: str = "baseline",
) -> Dict[str, Any]:
    """Lower + compile one (arch × shape × mesh) cell; return the record."""
    cfg = get_config(arch)
    if shape_name not in cfg.shapes():
        return {
            "arch": arch, "shape": shape_name, "mesh": "multi" if multi_pod else "single",
            "status": "SKIP",
            "reason": "long_500k requires sub-quadratic attention (DESIGN.md §7)",
        }
    shape = SHAPES[shape_name]
    force_host_devices()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules or DEFAULT_RULES
    t0 = time.time()
    ck = dict(remat_policy=remat_policy, grad_compress=grad_compress)

    # 1) REQUIRED compile proof + memory analysis: the full production model
    #    (scanned layer stack — memory-faithful).
    _, _, _, mem, compiled = _compile_once(cfg, shape_name, mesh, rules, unroll=False, **ck)
    # 2) Exact cost extrapolation from two reduced-depth unrolled compiles:
    #    cost(G) = fixed + G*body  (see module docstring).
    G = cfg.num_groups
    f1, b1, c1, _, _ = _compile_once(_reduced_depth(cfg, 1), shape_name, mesh, rules, unroll=True, **ck)
    f2, b2, c2, _, _ = _compile_once(_reduced_depth(cfg, 2), shape_name, mesh, rules, unroll=True, **ck)
    flops = f1 + (f2 - f1) * (G - 1)
    bytes_accessed = b1 + (b2 - b1) * (G - 1)
    coll: Dict[str, float] = {}
    for kind in set(c1) | set(c2):
        v1, v2 = c1.get(kind, 0), c2.get(kind, 0)
        coll[kind] = float(v1 + (v2 - v1) * (G - 1))
    n_chips = mesh.size
    coll_total = float(sum(coll.values()))

    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "tag": tag,
        "status": "OK",
        "n_chips": n_chips,
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "lower_compile_s": round(time.time() - t0, 1),
        "num_groups": cfg.num_groups,
        # cost_analysis is per-device under SPMD; extrapolated over depth
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll_total,
        "collectives": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
        },
        # roofline terms (seconds, per §ROOFLINE — per-chip quantities)
        "t_compute_s": flops / PEAK_FLOPS,
        "t_memory_s": bytes_accessed / HBM_BW,
        "t_collective_s": coll_total / ICI_BW,
    }
    terms = {
        "compute": record["t_compute_s"],
        "memory": record["t_memory_s"],
        "collective": record["t_collective_s"],
    }
    record["bottleneck"] = max(terms, key=terms.get)
    if save_artifact:
        d = artifact_dir or os.path.abspath(ARTIFACT_DIR)
        os.makedirs(d, exist_ok=True)
        fname = f"{tag}_{record['mesh']}_{arch.replace('/', '_')}_{shape_name}.json"
        with open(os.path.join(d, fname), "w") as f:
            json.dump(record, f, indent=1)
    return record


def plan_orchestration(
    scenario: str = "paper-table6",
    policy: str = "feasibility-aware",
    at_hour: float = 36.0,
    fill: float = 0.5,
    transfers: Tuple[Tuple[int, int], ...] = (),
):
    """Orchestration dry-run: scenario state at sim-time ``at_hour`` ->
    ClusterState (via the shared constructor) -> the policy's typed actions.

    Placement is synthetic but scenario-faithful: the earliest-arrived jobs
    run at their home sites, up to ``fill`` of each site's slots;
    ``transfers`` injects synthetic in-flight ``(src, dst)`` migrations so
    the preview can be taken under WAN load.  Every ``Migrate`` the policy
    proposes is re-checked at the **post-admission** ``(flows+1)`` rate —
    the advertised matrix is the current grant, systematically optimistic
    for a transfer the plan itself would add — and moves that are
    infeasible at the diluted rate are dropped from the plan.  Returns
    (state, actions)."""
    from repro.core import feasibility as fz
    from repro.core.actions import Migrate
    from repro.core.orchestrator import make_policy
    from repro.core.scenarios import get_scenario
    from repro.core.simulator import generate_jobs
    from repro.core.state import ClusterState, JobView, site_views_from_traces

    scn = get_scenario(scenario)
    cfg = scn.sim_config()
    traces = scn.build_traces()
    t = at_hour * 3600.0
    cap = max(1, int(round(cfg.slots_per_site * fill)))
    per_site = [0] * cfg.n_sites
    views = []
    for j in generate_jobs(cfg):
        if j.arrival_s > t or per_site[j.home_site] >= cap:
            continue
        views.append(JobView(j.jid, j.home_site, j.ckpt_bytes, j.compute_s))
        per_site[j.home_site] += 1
    sites = site_views_from_traces(traces, t, slots=cfg.slots_per_site,
                                   busy=per_site)
    # the same WanTopology the simulator materializes for this scenario
    # (per-link caps, asymmetric NICs, brownout calendar at sim-time t),
    # plus the forecast horizon (σ=0: the planner reads the calendar as-is)
    state = ClusterState.build(t, views, sites, wan=scn.build_wan(),
                               transfers=transfers, traces=traces,
                               signals=scn.build_signals(),
                               battery=cfg.battery)
    jobs_by_id = {j.jid: j for j in state.jobs}
    flows = list(transfers)
    actions = []
    for a in make_policy(policy).decide(state):
        if isinstance(a, Migrate):
            j = jobs_by_id[a.jid]
            rate = state.post_admission_bps(j.site, a.dest, flows)
            v = fz.evaluate(j.ckpt_bytes, rate,
                            state.site(a.dest).window_remaining_s,
                            t_load_s=j.t_load_s)
            if not bool(v.feasible):
                continue  # optimistic under load: drop from the plan
            flows.append((j.site, a.dest))
        actions.append(a)
    return state, actions


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all assigned)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--rules", default="baseline",
                    help="sharding strategy (parallel/strategies.py)")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--out", default=None)
    ap.add_argument("--plan", action="store_true",
                    help="orchestration plan preview instead of HLO lowering")
    ap.add_argument("--scenario", default="paper-table6")
    ap.add_argument("--policy", default="feasibility-aware")
    ap.add_argument("--at-hour", type=float, default=36.0)
    ap.add_argument("--transfers", default="",
                    help="synthetic in-flight migrations for --plan as "
                         "src:dst pairs, e.g. '0:2,0:3' — proposed moves "
                         "are admission-checked at the diluted "
                         "post-admission rate")
    args = ap.parse_args()

    if args.plan:
        transfers = tuple(
            (int(s), int(d)) for s, d in
            (pair.split(":") for pair in args.transfers.split(",") if pair))
        state, actions = plan_orchestration(args.scenario, args.policy,
                                            args.at_hour, transfers=transfers)
        print(f"[plan] scenario={args.scenario} policy={args.policy} "
              f"t={args.at_hour:.1f}h jobs={len(state.jobs)}")
        if state.battery is not None:
            b = state.battery
            sell = (f" sellback={b.sellback_kw:.1f}kW"
                    f"@floor=${b.sellback_price_floor:.2f}/kWh"
                    if b.sellback_kw > 0.0 else "")
            print(f"[plan] battery: {b.capacity_kwh:.0f} kWh/site, "
                  f"charge<={b.max_charge_kw:.1f}kW "
                  f"discharge<={b.max_discharge_kw:.1f}kW "
                  f"rte={b.round_trip_efficiency:.2f} "
                  f"dark-discharge>={b.discharge_threshold_g:.0f}g/kWh"
                  f"{sell}")
        for s in state.sites:
            print(f"[plan]   site{s.sid}: busy={s.busy} "
                  f"{'GREEN' if s.renewable_active else 'grid '} "
                  f"window={s.window_remaining_s / 3600:.2f}h")
        if not actions:
            print("[plan] no actions")
        for a in actions:
            print(f"[plan]   {a}")
        return 0

    archs = [args.arch] if args.arch else list(ASSIGNED)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    rows = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else list(SHAPES)
        for shape_name in shapes:
            for mp in meshes:
                try:
                    from repro.parallel.strategies import get_strategy

                    rec = lower_cell(
                        arch, shape_name, multi_pod=mp, remat_policy=args.remat,
                        grad_compress=args.grad_compress, tag=args.tag,
                        artifact_dir=args.out, rules=get_strategy(args.rules),
                    )
                except Exception as e:  # a failure here is a sharding bug
                    rec = {
                        "arch": arch, "shape": shape_name,
                        "mesh": "multi" if mp else "single",
                        "status": f"FAIL: {type(e).__name__}: {e}",
                    }
                rows.append(rec)
                status = rec["status"]
                extra = ""
                if status == "OK":
                    extra = (
                        f" t_comp={rec['t_compute_s']:.3f}s t_mem={rec['t_memory_s']:.3f}s"
                        f" t_coll={rec['t_collective_s']:.3f}s bound={rec['bottleneck']}"
                        f" peak={_fmt_bytes(rec['memory']['peak_bytes'])}"
                        f" ({rec['lower_compile_s']}s)"
                    )
                print(f"[dryrun] {arch:24s} {shape_name:12s} {rec['mesh']:6s} {status}{extra}", flush=True)
    n_ok = sum(1 for r in rows if r["status"] == "OK")
    n_skip = sum(1 for r in rows if r["status"] == "SKIP")
    n_fail = len(rows) - n_ok - n_skip
    print(f"[dryrun] done: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL")
    return 1 if n_fail else 0


def _fmt_bytes(b) -> str:
    if b is None:
        return "?"
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(b) < 1024:
            return f"{b:.1f}{unit}"
        b /= 1024
    return f"{b:.1f}PB"


if __name__ == "__main__":
    raise SystemExit(main())
