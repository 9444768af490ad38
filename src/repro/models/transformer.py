"""Decoder-only LM assembly: heterogeneous layer patterns (attention, Mamba,
m/sLSTM), MoE interleave, scan-over-layer-groups with configurable remat.

The layer stack is organized as ``num_groups`` repetitions of
``cfg.block_pattern``; group params are stacked on a leading dim and the
stack is applied with ``jax.lax.scan`` (one group's HLO, compiled once).
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import conv as conv_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import (
    apply_embed,
    apply_mlp,
    apply_norm,
    apply_unembed,
    cross_entropy,
    init_embed,
    init_mlp,
    init_norm,
    softcap,
    truncated_normal,
)
from repro.parallel.sharding import shd

REMAT_POLICIES = {
    "none": "none",
    "full": "full",
    "dots": "dots",
}


def unroll_scan() -> bool:
    """Dry-run accounting mode: python-unroll the layer-group loop so XLA
    cost_analysis and the HLO collective parse see every layer (XLA counts a
    While body once). Controlled by REPRO_UNROLL_SCAN=1 (set by dryrun.py)."""
    return os.environ.get("REPRO_UNROLL_SCAN", "0") == "1"


def scan_or_unroll(body, carry, xs):
    """lax.scan, or an equivalent unrolled python loop (cost accounting)."""
    if not unroll_scan():
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _is_moe_pos(cfg: ModelConfig, i: int) -> bool:
    if not cfg.moe:
        return False
    return (not cfg.moe_pattern) or (i in cfg.moe_pattern)


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, kind: str, moe_here: bool) -> dict:
    dt = _dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p: Dict[str, Any] = {"norm1": init_norm(cfg.d_model, cfg.norm_type, dt)}
    if kind.startswith("attn"):
        p["attn"] = attn_lib.init_attention(
            k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
            num_layers=cfg.num_layers, dtype=dt,
        )
    elif kind == "mamba":
        p["mamba"] = mamba_lib.init_mamba(
            k1, cfg.d_model, expand=cfg.mamba_expand, d_state=cfg.mamba_d_state,
            d_conv=cfg.mamba_d_conv, num_layers=cfg.num_layers, dtype=dt,
        )
    elif kind == "conv":
        p["conv"] = conv_lib.init_conv(k1, cfg.d_model, cfg.conv_kernel,
                                       cfg.num_layers, dt)
    elif kind == "mlstm":
        p["mlstm"] = xlstm_lib.init_mlstm(k1, cfg.d_model, cfg.num_heads, cfg.num_layers, dt)
        return p  # self-contained block
    elif kind == "slstm":
        p["slstm"] = xlstm_lib.init_slstm(k1, cfg.d_model, cfg.num_heads, cfg.num_layers, dt)
        return p
    else:
        raise ValueError(kind)
    p["norm2"] = init_norm(cfg.d_model, cfg.norm_type, dt)
    if moe_here:
        p["moe"] = moe_lib.init_moe(
            k2, cfg.d_model, cfg.expert_d_ff, cfg.num_experts, cfg.num_layers, dt,
            router_experts=cfg.router_experts, router_bias=cfg.router_bias,
        )
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.num_layers, dt)
    return p


def _mixer_kwargs(cfg: ModelConfig, kind: str) -> dict:
    return dict(
        rope_type=cfg.rope_type,
        rope_theta=cfg.rope_theta,
        mrope_sections=cfg.mrope_sections,
        qk_norm=cfg.qk_norm,
        mask_kind="window" if kind == "attn_local" else "causal",
        window=cfg.sliding_window if kind == "attn_local" else 0,
        attn_softcap=cfg.attn_softcap,
        norm_eps=cfg.rms_norm_eps,
    )


def _dropless(cfg: ModelConfig) -> bool:
    return cfg.moe and cfg.moe_impl == "dropless"


def _routed_share(cfg: ModelConfig) -> bool:
    """The router is ``moe.route``'s: sigmoid or biased scores, or more
    outputs than the experts held here."""
    return (cfg.router_score != "softmax" or cfg.router_bias
            or cfg.num_router_experts != cfg.num_experts)


def _no_rows(cfg: ModelConfig) -> dict:
    """Routed-row counts a block adds to the step's metrics: the dropless
    layer's rows computed here and its largest held expert's rows, summed
    over layers (nothing for other configs)."""
    if not _dropless(cfg):
        return {}
    return {"held": jnp.zeros((), jnp.int32), "max": jnp.zeros((), jnp.int32)}


def _apply_ffn(p: dict, h, cfg: ModelConfig, moe_here: bool):
    """(y, aux, rows) of the block's MLP or MoE."""
    if not moe_here:
        return apply_mlp(p["mlp"], h, cfg.act), jnp.zeros((), jnp.float32), _no_rows(cfg)
    routing = dict(top_k=cfg.top_k, score=cfg.router_score,
                   norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
                   first=cfg.first_expert)
    if _dropless(cfg):
        return moe_lib.apply_moe_dropless(p["moe"], h, act=cfg.act, **routing)
    if _routed_share(cfg):
        if cfg.moe_impl != "capacity":
            raise ValueError(f"{cfg.name}: a sigmoid or partly held router "
                             f"runs 'dropless' or 'capacity'")
        gates = moe_lib.share_gates(p["moe"], h, **routing)
        y, aux = moe_lib.apply_moe_capacity(p["moe"], h, top_k=cfg.top_k,
                                            act=cfg.act, gates=gates,
                                            num_router=cfg.num_router_experts)
        return y, aux, _no_rows(cfg)
    y, aux = moe_lib.apply_moe(p["moe"], h, top_k=cfg.top_k, act=cfg.act,
                               impl=cfg.moe_impl or None)
    return y, aux, _no_rows(cfg)


def apply_block(p: dict, x, kind: str, cfg: ModelConfig, positions, moe_here: bool):
    """Full-sequence block. Returns (x, aux_loss, rows)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(p["norm1"], x, cfg.norm_type, cfg.rms_norm_eps)
    if kind.startswith("attn"):
        mix = attn_lib.apply_attention(p["attn"], h, positions=positions, **_mixer_kwargs(cfg, kind))
    elif kind == "mamba":
        mix = mamba_lib.apply_mamba(p["mamba"], h, d_state=cfg.mamba_d_state)
    elif kind == "conv":
        mix = conv_lib.apply_conv(p["conv"], h)
    elif kind == "mlstm":
        return x + xlstm_lib.apply_mlstm(p["mlstm"], h, cfg.num_heads), aux, _no_rows(cfg)
    elif kind == "slstm":
        return x + xlstm_lib.apply_slstm(p["slstm"], h, cfg.num_heads), aux, _no_rows(cfg)
    else:
        raise ValueError(kind)
    x = x + mix
    h = apply_norm(p["norm2"], x, cfg.norm_type, cfg.rms_norm_eps)
    y, aux, rows = _apply_ffn(p, h, cfg, moe_here)
    x = x + y
    x = shd(x, "batch", "seq", "embed_act")
    return x, aux, rows


def apply_block_decode(p, x, kind, cfg, positions, index, cache, moe_here, long_context):
    """One-token block step. Returns (x, new_cache)."""
    h = apply_norm(p["norm1"], x, cfg.norm_type, cfg.rms_norm_eps)
    if kind.startswith("attn"):
        kw = _mixer_kwargs(cfg, kind)
        mix, cache = attn_lib.apply_attention_decode(
            p["attn"], h, cache, index, positions=positions,
            rope_type=kw["rope_type"], rope_theta=kw["rope_theta"],
            mrope_sections=kw["mrope_sections"], qk_norm=kw["qk_norm"],
            mask_kind=kw["mask_kind"], window=kw["window"],
            attn_softcap=kw["attn_softcap"], long_context=long_context,
            norm_eps=kw["norm_eps"],
        )
    elif kind == "mamba":
        mix, cache = mamba_lib.apply_mamba_decode(p["mamba"], h, cache, d_state=cfg.mamba_d_state)
    elif kind == "mlstm":
        y, cache = xlstm_lib.apply_mlstm(p["mlstm"], h, cfg.num_heads, state=cache, decode=True)
        return x + y, cache
    elif kind == "slstm":
        y, cache = xlstm_lib.apply_slstm(p["slstm"], h, cfg.num_heads, state=cache, decode=True)
        return x + y, cache
    else:
        raise ValueError(kind)
    x = x + mix
    h = apply_norm(p["norm2"], x, cfg.norm_type, cfg.rms_norm_eps)
    y, _, _ = _apply_ffn(p, h, cfg, moe_here)
    return x + y, cache


# ---------------------------------------------------------------------------
# LM init / forward
# ---------------------------------------------------------------------------


def init_lm(key, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    ke, ku, kp, kg = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    params["embed"] = init_embed(ke, cfg.vocab_size, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        params["unembed"] = {"table": truncated_normal(ku, (cfg.d_model, cfg.vocab_size), 0.02, dt)}
    if cfg.learned_pos:
        params["pos_embed"] = {"table": truncated_normal(kp, (32768, cfg.d_model), 0.02, dt)}

    def init_group(gkey):
        ks = jax.random.split(gkey, len(cfg.block_pattern))
        return {
            f"b{i}": init_block(ks[i], cfg, kind, _is_moe_pos(cfg, i))
            for i, kind in enumerate(cfg.block_pattern)
        }

    gkeys = jax.random.split(kg, cfg.num_groups)
    params["groups"] = jax.vmap(init_group)(gkeys)
    params["final_norm"] = init_norm(cfg.d_model, cfg.norm_type, dt)
    return params


def embed_inputs(params, cfg: ModelConfig, batch: dict):
    if "embeds" in batch:
        x = batch["embeds"].astype(_dtype(cfg))
    else:
        x = apply_embed(params["embed"], batch["tokens"])
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.learned_pos:
        pos = batch["positions"] if "positions" in batch else jnp.arange(x.shape[1], dtype=jnp.int32)[None]
        if pos.ndim == 3:
            pos = pos[..., 0]
        pe = jnp.take(params["pos_embed"]["table"], pos, axis=0)
        x = x + jnp.broadcast_to(pe, x.shape).astype(x.dtype)
    return shd(x, "batch", "seq", "embed_act")


def lm_forward(
    params: dict,
    batch: dict,
    cfg: ModelConfig,
    *,
    remat_policy: str = "full",
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (b, s, vocab), aux_loss)."""
    logits, aux, _ = _lm_forward(params, batch, cfg, remat_policy)
    return logits, aux


def _lm_forward(params, batch, cfg: ModelConfig, remat_policy: str):
    """(logits, aux_loss, rows): ``rows`` sums the blocks' routed-row
    counts (empty unless the MoE is dropless)."""
    x = embed_inputs(params, cfg, batch)
    b, s = x.shape[0], x.shape[1]
    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def block_fn(i, kind):
        def fn(p, x):
            return apply_block(p, x, kind, cfg, positions, _is_moe_pos(cfg, i))
        # a group of several blocks keeps one block's intermediates at a
        # time in the backward pass: remat goes per block
        return _remat(fn, remat_policy) if len(cfg.block_pattern) > 1 else fn

    blocks = [block_fn(i, kind) for i, kind in enumerate(cfg.block_pattern)]

    def group_fn(carry, gp):
        x, aux, rows = carry
        for i, fn in enumerate(blocks):
            x, a, r = fn(gp[f"b{i}"], x)
            aux = aux + a
            rows = jax.tree.map(jnp.add, rows, r)
        return (x, aux, rows), None

    body = group_fn if len(cfg.block_pattern) > 1 else _remat(group_fn, remat_policy)
    init = (x, jnp.zeros((), jnp.float32), _no_rows(cfg))
    (x, aux, rows), _ = scan_or_unroll(body, init, params["groups"])
    x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.rms_norm_eps)
    table = params["embed"]["table"].T if cfg.tie_embeddings else params["unembed"]["table"]
    logits = apply_unembed(table, x)
    logits = softcap(logits, cfg.logit_softcap)
    return logits, aux, rows


def lm_loss(params, batch, cfg: ModelConfig, *, remat_policy: str = "full"):
    logits, aux, rows = _lm_forward(params, batch, cfg, remat_policy)
    ce = cross_entropy(logits, batch["labels"])
    loss = ce + cfg.router_aux_coef * aux
    metrics = {"ce": ce, "aux": aux}
    if rows:
        metrics.update(moe_rows_held=rows["held"], moe_rows_max=rows["max"])
    return loss, metrics


# ---------------------------------------------------------------------------
# Cache / decode
# ---------------------------------------------------------------------------


def _block_cache_spec(cfg: ModelConfig, kind: str, batch: int, max_len: int, long: bool):
    dt = _dtype(cfg)
    if kind.startswith("attn"):
        cache_len = min(max_len, cfg.sliding_window) if kind == "attn_local" and cfg.sliding_window else max_len
        shape = (batch, cache_len, cfg.num_kv_heads, cfg.resolved_head_dim)
        sds = {"k": jax.ShapeDtypeStruct(shape, dt), "v": jax.ShapeDtypeStruct(shape, dt)}
        ax = ("kv_long" if long else "kv_seq")
        ps = (None if long else "dp_batch", ax, None, None)
        return sds, {"k": ps, "v": ps}
    if kind == "mamba":
        return mamba_lib.mamba_state_spec(
            batch, cfg.d_model, expand=cfg.mamba_expand, d_state=cfg.mamba_d_state,
            d_conv=cfg.mamba_d_conv, dtype=dt, long_context=long,
        )
    if kind == "mlstm":
        return xlstm_lib.mlstm_state_spec(batch, cfg.d_model, cfg.num_heads, long_context=long)
    if kind == "slstm":
        return xlstm_lib.slstm_state_spec(batch, cfg.d_model, long_context=long)
    raise ValueError(kind)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int, long_context: bool = False):
    """(ShapeDtypeStruct pytree, logical-pspec pytree) for the decode cache,
    with the leading stacked group dim."""

    def stack_sds(sds):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.num_groups,) + s.shape, s.dtype), sds
        )

    def stack_ps(ps):
        return jax.tree.map(
            lambda p: ("layers",) + tuple(p),
            ps,
            is_leaf=lambda x: isinstance(x, tuple) and (not x or not isinstance(x[0], tuple)),
        )

    specs, pspecs = {}, {}
    for i, kind in enumerate(cfg.block_pattern):
        sds, ps = _block_cache_spec(cfg, kind, batch, max_len, long_context)
        specs[f"b{i}"] = stack_sds(sds)
        pspecs[f"b{i}"] = stack_ps(ps)
    return specs, pspecs


def init_cache(cfg: ModelConfig, batch: int, max_len: int, long_context: bool = False):
    specs, _ = cache_specs(cfg, batch, max_len, long_context)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)


def lm_decode_step(
    params: dict,
    cache: dict,
    batch: dict,  # {'token': (b,) int32 | 'embeds': (b,1,d), 'index': scalar, ['positions']}
    cfg: ModelConfig,
    *,
    long_context: bool = False,
):
    """One-token decode. Returns (logits (b, vocab), new_cache)."""
    index = batch["index"].astype(jnp.int32)
    if "embeds" in batch:
        x = embed_inputs(params, cfg, {"embeds": batch["embeds"],
                                       **({"positions": batch["positions"]} if "positions" in batch else {})})
    else:
        tok = batch["token"][:, None]
        pb = {"tokens": tok}
        if "positions" in batch:
            pb["positions"] = batch["positions"]
        elif cfg.learned_pos:
            pb["positions"] = jnp.broadcast_to(index[None, None], (tok.shape[0], 1))
        x = embed_inputs(params, cfg, pb)
    b = x.shape[0]
    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(index[None, None], (b, 1)).astype(jnp.int32)

    def group_fn(x, xs):
        gp, gc = xs
        new_c = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, new_c[f"b{i}"] = apply_block_decode(
                gp[f"b{i}"], x, kind, cfg, positions, index, gc[f"b{i}"],
                _is_moe_pos(cfg, i), long_context,
            )
        return x, new_c

    x, new_cache = scan_or_unroll(group_fn, x, (params["groups"], cache))
    x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.rms_norm_eps)
    table = params["embed"]["table"].T if cfg.tie_embeddings else params["unembed"]["table"]
    logits = apply_unembed(table, x)
    logits = softcap(logits, cfg.logit_softcap)
    return logits[:, 0], new_cache
