"""Window driver: training steps of an expert-share hybrid (LFM2-MoE)
back to back through ``Trainer.run``.

As ``train_steps``, whose ``window``, ``readings``, ``check`` and
``close`` it runs unchanged: set-up builds one trainer on weights the
configuration's reference makes from the seed and drives it through its
first three steps; the window calls ``run`` for ``steps_per_call`` steps
at a time; the check compares the first three steps with the reference
at ``Precision.HIGHEST``.  What differs is how the program is built and
held:

- the program's ``ModelConfig`` comes from the configuration's own keys
  (layer types, dense layers, the router over all experts, the experts
  held here), with the dropless expert layer;
- the trainer donates its state to each step (the state fits on the
  chip once, not twice), so the weights before step 1, AdamW's first
  moment after it and the weights after step 3 are copied to the host
  for the check;
- a traced run names the step's operations under each program scope
  (``moe.route``, ``moe.experts``, ``moe.combine``, ``conv``) from the
  compiled step's text, for the readers of per-layer metrics
  (``ctx.info["scoped_ops"]``);
- the check also prints, as a reading that is not compared, how many
  tokens of the first step's first rows chose other experts in the
  program than in the reference.

Variants: ``control`` (the reference one precision lower in the
program's place, as ``train_steps``) and ``dropping`` (the program with
the capacity dispatch, which drops overflow rows, in place of the
dropless layer).

Traffic parameters: as ``train_steps``.
"""
from __future__ import annotations

import os
import re
import sys
from typing import Any, Dict

import numpy as np

from harness import lm, spec
from harness.runner import Context

TS = spec.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "train_steps.py"), "bench_driver_train_steps")
window, readings, close = TS.window, TS.readings, TS.close
FIRST_STEPS = TS.FIRST_STEPS
SCOPES = ("moe.route", "moe.experts", "moe.combine", "conv")
KINDS = {"conv": "conv", "full_attention": "attn"}


def program_config(c: Dict[str, Any], moe_impl: str = "dropless"):
    """The program's ``ModelConfig`` for an LFM2-MoE configuration file,
    whose keys are those of the model's published ``config.json``."""
    from repro.configs.base import ModelConfig

    L, dense = c["num_hidden_layers"], c["num_dense_layers"]
    return ModelConfig(
        name=c["name"], family="hybrid", num_layers=L,
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=lm.head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], qk_norm=True,
        block_pattern=tuple(KINDS[k] for k in c["layer_types"][:L]),
        moe_pattern=tuple(range(dense, L)), moe=True,
        num_experts=c["num_experts"], router_experts=c["router_experts"],
        first_expert=c["first_expert"], top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"], router_aux_coef=0.0,
        router_score="sigmoid", router_bias=c["use_expert_bias"],
        norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        moe_impl=moe_impl, conv_kernel=c["conv_L_cache"],
        rms_norm_eps=c["norm_eps"], norm_type="rmsnorm", act=c["hidden_act"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        source=c["source"])


def trainer(model, data, root: str, c: Dict[str, Any], seed: int):
    """``lm.trainer``'s trainer, donating its state to each step."""
    import dataclasses

    tr = lm.trainer(model, data, root, c, "full", seed)
    tr.cfg = dataclasses.replace(tr.cfg, donate_state=True)
    return type(tr)(model, data, tr.ckpt, tr.cfg)


def scoped_ops(hlo_text: str) -> Dict[str, list]:
    """The instructions of a compiled program's text whose ``op_name``
    passes through each of ``SCOPES``."""
    out: Dict[str, set] = {s: set() for s in SCOPES}
    # a scope is a component of the name, or wrapped by a transformation
    # (``transpose(jvp(moe.experts))``)
    scope = {s: re.compile(r"(^|[/(])" + re.escape(s) + r"($|[/)])")
             for s in SCOPES}
    for m in re.finditer(r'^\s*(?:ROOT )?%(\S+) = [^\n]*?op_name="([^"]*)"',
                         hlo_text, re.M):
        for s in SCOPES:
            if scope[s].search(m.group(2)):
                out[s].add(m.group(1))
    return {s: sorted(v) for s, v in out.items()}


def setup(ctx: Context) -> Dict[str, Any]:
    import jax

    from repro.models.model import build_model
    from repro.optim.adamw import init_opt_state

    c, t = ctx.config, ctx.traffic
    ref = spec.reference(ctx.cell)
    impl = "capacity" if ctx.variant == "dropping" else "dropless"
    model = build_model(program_config(c, impl))
    params0 = lm.make_weights(ref, c, ctx.subseed(1))
    lm.check_layout(model, params0)
    data = lm.Tokens(c["vocab_size"], t["seq"], t["batch"], ctx.subseed(2))
    tr = trainer(model, data, os.path.join(ctx.workdir, "site-a"), c,
                 ctx.subseed(3))
    tr.train_step = ctx.plant("train_step", tr.train_step)
    st: Dict[str, Any] = {"ref": ref, "data": data, "trainer": tr,
                          "model": model, "params0": jax.device_get(params0)}
    tr.params, tr.opt_state, tr.step = params0, init_opt_state(params0), 0
    del params0  # donated to the first step
    for i in range(FIRST_STEPS):
        tr.run(max_steps=1)
        if i == 0:
            st["m1"] = jax.device_get(tr.opt_state["m"])
    st["params3"] = jax.device_get(tr.params)
    st["losses"] = [row["loss"] for row in tr.history[:FIRST_STEPS]]
    if ctx.traced and hasattr(tr.train_step, "lower"):
        ctx.info["scoped_ops"] = scoped_ops(tr.train_step.lower(
            tr.params, tr.opt_state, data.batch(0)).compile().as_text())
    jax.block_until_ready(tr.params)
    return st


def program_choices(cfg, params, tokens) -> np.ndarray:
    """The program's router choices (b, s, MoE layers, R) bool, from its
    own blocks and router, at its own precision."""
    import jax
    import jax.numpy as jnp

    from repro.models import attention as attn_lib
    from repro.models import conv as conv_lib
    from repro.models import moe as moe_lib
    from repro.models import transformer as tfm
    from repro.models.layers import apply_norm

    def fn(params, tokens):
        x = tfm.embed_inputs(params, cfg, {"tokens": tokens})
        b, s = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        out = []
        for i, kind in enumerate(cfg.block_pattern):
            p = jax.tree.map(lambda w: w[0], params["groups"][f"b{i}"])
            if tfm._is_moe_pos(cfg, i):
                h = apply_norm(p["norm1"], x, cfg.norm_type, cfg.rms_norm_eps)
                mix = (conv_lib.apply_conv(p["conv"], h) if kind == "conv" else
                       attn_lib.apply_attention(p["attn"], h, positions=pos,
                                                **tfm._mixer_kwargs(cfg, kind)))
                h = apply_norm(p["norm2"], x + mix, cfg.norm_type,
                               cfg.rms_norm_eps)
                idx, _ = moe_lib.route(
                    p["moe"], h.reshape(b * s, -1), top_k=cfg.top_k,
                    score=cfg.router_score, norm_topk=cfg.norm_topk_prob,
                    scale=cfg.routed_scaling_factor)
                out.append(jax.nn.one_hot(idx, cfg.num_router_experts,
                                          dtype=jnp.int32).sum(1).reshape(b, s, -1) > 0)
            x = tfm.apply_block(p, x, kind, cfg, pos, tfm._is_moe_pos(cfg, i))[0]
        return jnp.stack(out, axis=2)

    return np.asarray(jax.jit(fn)(params, jnp.asarray(tokens)))


def check(ctx: Context, st: Dict[str, Any]):
    out = TS.check(ctx, st)
    rows = ctx.traffic["ref_rows"]
    toks = st["batches"][0]["tokens"][:rows]
    got = program_choices(st["model"].cfg, st["params0"], toks)
    want = st["ref"].batch_choices(st["params0"], {"tokens": toks},
                                   ctx.config, rows)
    differ = np.any(got != want, axis=-1)  # (b, s, MoE layers)
    print(f"[bench] routing at step 1, first {rows} row(s): "
          f"{int(np.any(differ, axis=-1).sum())} of {differ.shape[0] * differ.shape[1]} "
          f"tokens chose another expert in the program than in the "
          f"reference in some layer ({int(differ.sum())} of {differ.size} "
          f"token-layers); not compared",
          file=sys.stderr, flush=True)
    return out
