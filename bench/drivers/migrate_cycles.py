"""Window driver: a training job migrated between two sites, over and
over.

One cycle: a training step at the source; the migration order; then,
timed as one resume interval, ``Trainer.save`` (device-to-host gather,
serialize, write), ``migrate_job`` to the destination's directory
(export and import; the WAN leg is modelled, not slept), a new
``Trainer`` at the destination as ``repro.launch.train --resume`` builds
it, ``Trainer.restore``, and the first training step there, whose
metrics the program reads back.  The destination is the next cycle's
source.  Checkpoints live on local disk inside the checkout and each is
removed once it has been restored.

After each interval the restored state is compared, bit for bit, with
the source's state at the order, and the destination's parameters after
its first step with the restored ones (every leaf has to move).  After
the window, the last cycle's first loss at the destination is read
against the reference's loss on the source's parameters at the order,
and compared where the traffic's ``limits`` name it.

Traffic parameters: ``batch``, ``seq``, ``ckpt_mode``, ``wan_gbps``,
``trace_seconds``, ``ref_rows``, ``limits``; the control variant saves
in ``control_ckpt_mode``.
"""
from __future__ import annotations

import math
import os
import shutil
import sys
import time
from typing import Any, Dict

import numpy as np

from harness import lm, spec
from harness.runner import Check, Context


def _same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)))


def setup(ctx: Context) -> Dict[str, Any]:
    import jax

    from repro.models.model import build_model
    from repro.optim.adamw import init_opt_state

    c, t = ctx.config, ctx.traffic
    ref = spec.reference(ctx.cell)
    mode = t["control_ckpt_mode"] if ctx.variant == "control" else t["ckpt_mode"]
    model = build_model(lm.program_config(c))
    params0 = lm.make_weights(ref, c, ctx.subseed(1))
    lm.check_layout(model, params0)
    data = lm.Tokens(c["vocab_size"], t["seq"], t["batch"], ctx.subseed(2))
    sites = [os.path.join(ctx.workdir, "site-a"),
             os.path.join(ctx.workdir, "site-b")]
    tr = lm.trainer(model, data, sites[0], c, mode, ctx.subseed(3))
    # the eager parameter init a restoring trainer runs, warmed once
    tr.init_state()
    tr.params, tr.opt_state, tr.step = params0, init_opt_state(params0), 0
    tr.run(max_steps=1)
    jax.block_until_ready(tr.params)
    return {"ref": ref, "model": model, "data": data, "mode": mode,
            "sites": sites, "src": tr, "src_site": 0, "intervals": [],
            "differ": 0, "unmoved": 0, "cycles": 0}


def _cycle(ctx: Context, st: Dict[str, Any]) -> None:
    import jax

    from repro.core.migration import migrate_job
    from repro.train.trainer import Trainer

    probe, t = ctx.probe, ctx.traffic
    src = st["src"]
    dst_dir = st["sites"][1 - st["src_site"]]
    with probe.span("source_step"):
        src.run(max_steps=1)
    t0 = time.perf_counter()
    with probe.span("resume"):
        with probe.span("save"):
            ctx.plant("save", src.save)()
        with probe.span("migrate_job"):
            dst_mgr, report = migrate_job(src.ckpt, dst_dir,
                                          bandwidth_bps=t["wan_gbps"] * 1e9)
        with probe.span("trainer_build"):
            dst = Trainer(src.model, src.dataset, dst_mgr, src.cfg)
        with probe.span("restore"):
            ctx.plant("restore", dst.restore)()
        restored = dst.state_tree()
        with probe.span("first_step"):
            ctx.plant("first_step", dst.run)(max_steps=1)
    st["intervals"].append(time.perf_counter() - t0)
    st["wan_s"] = report.t_transfer_s
    st["nbytes"] = report.nbytes
    with probe.span("verify"):
        before = jax.device_get(src.state_tree())
        st["differ"] += sum(not _same(a, b) for a, b in zip(
            jax.tree.leaves(before), jax.tree.leaves(restored)))
        after = jax.device_get(dst.params)
        st["unmoved"] += sum(_same(a, b) for a, b in zip(
            jax.tree.leaves(after), jax.tree.leaves(restored["params"])))
    st["last"] = {"params": src.params, "step": int(restored["step"]),
                  "loss": dst.history[-1]["loss"]}
    shutil.rmtree(os.path.join(st["sites"][st["src_site"]], src.ckpt.job),
                  ignore_errors=True)
    shutil.rmtree(os.path.join(dst_dir, src.ckpt.job), ignore_errors=True)
    st["src"], st["src_site"] = dst, 1 - st["src_site"]
    st["cycles"] += 1


def window(ctx: Context, st: Dict[str, Any]) -> Dict[str, float]:
    seconds = ctx.seconds
    if ctx.traced:
        seconds = min(seconds, ctx.traffic["trace_seconds"])
    t0 = time.perf_counter()
    while True:
        _cycle(ctx, st)
        if time.perf_counter() - t0 >= seconds:
            break
    for name in ("save", "migrate_job", "restore"):
        ctx.info["span_" + name] = list(ctx.probe.spans[name])
    print(f"[bench] {st['cycles']} migration(s) of {st['nbytes']} bytes "
          f"({st['mode']}); modelled WAN leg at {ctx.traffic['wan_gbps']} "
          f"Gbps {st['wan_s']:.3f} s each, not counted; resume intervals "
          f"{st['intervals']}", file=sys.stderr, flush=True)
    return {"resume_s": sum(st["intervals"]) / len(st["intervals"])}


def check(ctx: Context, st: Dict[str, Any]):
    last = st["last"]
    st["src"] = None  # the program's live state is freed first
    batch = st["data"].batch(last["step"])
    want = st["ref"].mean_loss(last["params"], batch, ctx.config,
                               ctx.traffic["ref_rows"])
    gap = abs(last["loss"] - want) if math.isfinite(last["loss"]) else math.inf
    print(f"[bench] first loss at the destination {last['loss']} at step "
          f"{last['step'] + 1}, reference {want}, resume_loss_gap {gap!r}",
          file=sys.stderr, flush=True)
    limits = ctx.traffic["limits"]
    # a number is compared where the traffic gives it a limit
    found = {"leaves_differ": st["differ"], "leaves_unmoved": st["unmoved"],
             "resume_loss_gap": gap}
    checks = [Check(k, v, limits[k]) for k, v in found.items() if k in limits]
    failed = min(st["cycles"], int(st["differ"] > 0) + int(st["unmoved"] > 0))
    return st["cycles"], failed, checks


def close(ctx: Context, st: Dict[str, Any]) -> None:
    for d in st.get("sites", ()):
        shutil.rmtree(d, ignore_errors=True)
    st.clear()
