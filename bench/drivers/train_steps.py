"""Window driver: training steps back to back through ``Trainer.run``.

Set-up builds one trainer (the program's jitted train step with its
state) on weights the configuration's reference makes from the seed, and
drives it through its first three steps with the window's own call,
``Trainer.run(max_steps=...)``.  The window hands that same trainer on
and calls ``run`` for ``steps_per_call`` steps at a time until the
window's seconds are spent; each call ends with the program reading its
last step's metrics, which waits for the device.  No save falls inside.

The check reads the first three steps against the reference at
``Precision.HIGHEST``: each step's loss, the first gradient as the
optimizer got it (from AdamW's first moment after step 1) and the
parameters' change after step 3, each by its worst leaf; it compares
those that the traffic's ``limits`` name.

Traffic parameters: ``batch``, ``seq``, ``steps_per_call``,
``trace_seconds``, ``ref_rows`` (rows per block of the reference),
``limits``.
"""
from __future__ import annotations

import math
import os
import time
from typing import Any, Dict

import numpy as np

from harness import lm, spec
from harness.runner import Check, Context

FIRST_STEPS = 3


def setup(ctx: Context) -> Dict[str, Any]:
    import jax

    from repro.models.model import build_model
    from repro.optim.adamw import init_opt_state

    c, t = ctx.config, ctx.traffic
    ref = spec.reference(ctx.cell)
    model = build_model(lm.program_config(c))
    params0 = lm.make_weights(ref, c, ctx.subseed(1))
    lm.check_layout(model, params0)
    data = lm.Tokens(c["vocab_size"], t["seq"], t["batch"], ctx.subseed(2))
    tr = lm.trainer(model, data, os.path.join(ctx.workdir, "site-a"), c,
                    "full", ctx.subseed(3))
    tr.train_step = ctx.plant("train_step", tr.train_step)
    tr.params, tr.opt_state, tr.step = params0, init_opt_state(params0), 0
    st: Dict[str, Any] = {"ref": ref, "data": data, "trainer": tr,
                          "params0": params0}
    for i in range(FIRST_STEPS):
        tr.run(max_steps=1)
        if i == 0:
            st["m1"] = tr.opt_state["m"]
    st["params3"] = tr.params
    st["losses"] = [row["loss"] for row in tr.history[:FIRST_STEPS]]
    jax.block_until_ready(tr.params)
    return st


def window(ctx: Context, st: Dict[str, Any]) -> Dict[str, float]:
    tr, t = st["trainer"], ctx.traffic
    seconds = ctx.seconds
    if ctx.traced:
        seconds = min(seconds, t["trace_seconds"])
    k = t["steps_per_call"]
    first = len(tr.history)
    t0 = time.perf_counter()
    steps = 0
    while True:
        with ctx.probe.span("train_call"):
            tr.run(max_steps=k)
        steps += k
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    tokens = steps * t["batch"] * t["seq"]
    st["window_losses"] = [row["loss"] for row in tr.history[first:]]
    st["steps"] = steps
    ctx.info.update(tokens=tokens, wall_s=wall, steps=steps)
    return {"train_tokens_per_s": tokens / wall}


def readings(ctx: Context, st: Dict[str, Any], losses, m1, params3):
    """The three compared numbers of a trajectory against the
    reference's."""
    import jax
    import jax.numpy as jnp

    c = ctx.config
    ref, rows = st["ref"], ctx.traffic["ref_rows"]
    if "ref_traj" not in st:
        batches = [st["data"].batch(i) for i in range(FIRST_STEPS)]
        st["batches"] = batches
        st["ref_traj"] = ref.train(st["params0"], batches, c, rows)
    r_losses, r_g1, r_p3 = st["ref_traj"]
    g1 = jax.tree.map(lambda m: m / (1.0 - c["train"]["b1"]), m1)
    want_g = lm.leaf_norms(r_g1)
    med = float(np.median(list(want_g.values())))
    moving = {k for k, v in want_g.items() if v >= 1e-3 * med}
    p0 = st["params0"]
    got_d = lm.leaf_norms(jax.tree.map(jnp.subtract, params3, p0))
    want_d = lm.leaf_norms(jax.tree.map(jnp.subtract, r_p3, p0))
    loss_gap = max(abs(a - b) for a, b in zip(losses, r_losses))
    if not all(math.isfinite(x) for x in losses):
        loss_gap = math.inf
    return {"loss_gap": loss_gap,
            "grad_gap": lm.worst_leaf_gap(lm.leaf_norms(g1), want_g),
            "update_gap": lm.worst_leaf_gap(got_d, want_d, moving),
            "leaves_left_out": len(want_g) - len(moving)}


def control_trajectory(ctx: Context, st: Dict[str, Any]):
    """The reference one precision below the configuration's float32
    (bfloat16 model, float32 optimizer), in the program's place."""
    import jax
    import jax.numpy as jnp

    c, ref = ctx.config, st["ref"]
    losses, g1, p3 = ref.train(st["params0"], st["batches"], c,
                               ctx.traffic["ref_rows"],
                               prec=jax.lax.Precision.DEFAULT,
                               dtype=jnp.bfloat16)
    m1 = jax.tree.map(lambda g: g * (1.0 - c["train"]["b1"]), g1)
    return losses, m1, p3


def check(ctx: Context, st: Dict[str, Any]):
    tr = st.pop("trainer")
    del tr  # the program's live state is freed before the reference runs
    r = readings(ctx, st, st["losses"], st["m1"], st["params3"])
    if ctx.variant == "control":
        r = readings(ctx, st, *control_trajectory(ctx, st))
    limits = ctx.traffic["limits"]
    failed = sum(1 for x in st["window_losses"] if not math.isfinite(x))
    print(f"[bench] {st['steps']} steps in the window; first losses "
          f"{st['losses']} vs reference {st['ref_traj'][0]}; "
          f"{r['leaves_left_out']} leaves with a reference gradient under "
          f"1e-3 of the median left out of update_gap; readings "
          f"loss_gap {r['loss_gap']!r}, grad_gap {r['grad_gap']!r}, "
          f"update_gap {r['update_gap']!r}",
          file=__import__("sys").stderr, flush=True)
    # a number is compared where the traffic gives it a limit
    checks = [Check(k, r[k], limits[k])
              for k in ("loss_gap", "grad_gap", "update_gap") if k in limits]
    return st["steps"], failed, checks


def close(ctx: Context, st: Dict[str, Any]) -> None:
    st.clear()
