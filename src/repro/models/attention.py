"""GQA attention with RoPE/M-RoPE, sliding windows, soft-capping, qk-norm,
and a KV-cache decode path.

The quadratic reference math lives here (and doubles as the XLA path used on
CPU / in the dry-run); `repro.kernels.ops.flash_attention` is the Pallas TPU
fast path for train/prefill and is selected automatically on TPU backends.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import rope as rope_lib
from repro.models.layers import rms_norm_1d, truncated_normal
from repro.parallel.sharding import shd

NEG_INF = -2.0e38


def init_attention(
    key,
    d_model: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    *,
    qkv_bias: bool,
    qk_norm: bool,
    num_layers: int,
    dtype,
) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    out_std = 0.02 / max(1.0, (2.0 * num_layers) ** 0.5)
    p = {
        "wq": truncated_normal(kq, (d_model, num_heads, head_dim), 0.02, dtype),
        "wk": truncated_normal(kk, (d_model, num_kv_heads, head_dim), 0.02, dtype),
        "wv": truncated_normal(kv, (d_model, num_kv_heads, head_dim), 0.02, dtype),
        "wo": truncated_normal(ko, (num_heads, head_dim, d_model), out_std, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((num_kv_heads, head_dim), dtype)
        p["bv"] = jnp.zeros((num_kv_heads, head_dim), dtype)
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def _mask_bias(qpos, kpos, mask_kind: str, window: int) -> Optional[jax.Array]:
    """Additive mask bias broadcastable to (..., q, k). qpos/kpos int32."""
    if mask_kind == "full":
        return None
    ok = kpos[..., None, :] <= qpos[..., :, None]
    if mask_kind == "window" and window > 0:
        ok &= (qpos[..., :, None] - kpos[..., None, :]) < window
    return jnp.where(ok, 0.0, NEG_INF)


def attend_ref(
    q: jax.Array,  # (b, s, nh, hd)
    k: jax.Array,  # (b, t, nkv, hd)
    v: jax.Array,  # (b, t, nkv, hd)
    *,
    mask_kind: str,
    window: int = 0,
    attn_softcap: float = 0.0,
    qpos: Optional[jax.Array] = None,  # (b, s)
    kpos: Optional[jax.Array] = None,  # (b, t)
    kv_valid: Optional[jax.Array] = None,  # (b, t) bool — decode cache validity
    kv_seq_axis: Optional[str] = None,  # keep scores sharded over the cache
    # sequence (flash-decode): partial softmax + tiny all-reduces instead of
    # all-gathering the K/V cache (§Perf decode optimization)
) -> jax.Array:
    """Quadratic GQA attention, f32 softmax. Returns (b, s, nh, hd)."""
    b, s, nh, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    qg = q.reshape(b, s, nkv, g, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    if attn_softcap:
        scores = attn_softcap * jnp.tanh(scores / attn_softcap)
    if qpos is None:
        qpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if kpos is None:
        kpos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    bias = _mask_bias(qpos, kpos, mask_kind, window)  # (b, s, t) or None
    if bias is not None:
        scores = scores + bias[:, None, None, :, :]
    if kv_valid is not None:
        scores = jnp.where(kv_valid[:, None, None, None, :], scores, NEG_INF)
    if kv_seq_axis is not None:
        scores = shd(scores, "*", "*", "*", "*", kv_seq_axis)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, s, nh, hd)


def _project_qkv(p, x, kv_x, *, qk_norm, norm_eps=1e-6):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", kv_x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", kv_x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if qk_norm:
        q = rms_norm_1d(q, p["q_norm"], norm_eps)
        k = rms_norm_1d(k, p["k_norm"], norm_eps)
    return q, k, v


def apply_attention(
    p: dict,
    x: jax.Array,  # (b, s, d)
    *,
    positions: jax.Array,  # (b, s) or (b, s, 3)
    rope_type: str,
    rope_theta: float,
    mrope_sections=(),
    qk_norm: bool = False,
    mask_kind: str = "causal",  # 'causal' | 'window' | 'full'
    window: int = 0,
    attn_softcap: float = 0.0,
    norm_eps: float = 1e-6,
) -> jax.Array:
    """Self-attention over a full sequence (train / prefill)."""
    q, k, v = _project_qkv(p, x, x, qk_norm=qk_norm, norm_eps=norm_eps)
    q = rope_lib.apply_positional(q, positions, rope_type, rope_theta, mrope_sections)
    k = rope_lib.apply_positional(k, positions, rope_type, rope_theta, mrope_sections)
    q = shd(q, "batch", "seq", "heads_act", "head_dim")
    # K/V explicitly replicated over the seq shards. §Perf iteration A3
    # tested leaving them unconstrained (hoping for a reduce-scatter
    # backward): REFUTED — GSPMD then chose all-to-all + larger gathers
    # (t_coll 15.0 -> 21.8 s on qwen1.5-32b/train_4k). Keep the constraint.
    if os.environ.get("REPRO_KV_REPLICATE", "1") == "1":
        k = shd(k, "batch", None, "heads_act", "head_dim")
        v = shd(v, "batch", None, "heads_act", "head_dim")

    from repro.kernels import ops as kernel_ops  # lazy: avoids cycle

    # Mask positions are *sequence indices* (dense left-aligned batches);
    # rope positions may be arbitrary (M-RoPE t/h/w streams).
    b, s = x.shape[0], x.shape[1]
    mask_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    out = kernel_ops.flash_attention(
        q, k, v,
        mask_kind=mask_kind, window=window, attn_softcap=attn_softcap,
        qpos=mask_pos, kpos=mask_pos,
    )
    out = shd(out, "batch", "seq", "heads_act", "head_dim")
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def apply_cross_attention(
    p: dict,
    x: jax.Array,  # (b, s, d) decoder stream
    kv: jax.Array,  # (b, t, nkv, hd) x2 precomputed, or raw (b, t, d)
) -> jax.Array:
    if isinstance(kv, tuple):
        k, v = kv
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if "bq" in p:
            q = q + p["bq"]
    else:
        q, k, v = _project_qkv(p, x, kv, qk_norm=False)
    out = attend_ref(q, k, v, mask_kind="full")
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_kv(p: dict, enc_out: jax.Array):
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return k, v


# ---------------------------------------------------------------------------
# Decode path (single new token against a KV cache)
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int, dtype):
    return {
        "k": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, num_kv_heads, head_dim), dtype),
    }


def kv_cache_spec(batch, max_len, num_kv_heads, head_dim, dtype, long_context=False):
    shape = (batch, max_len, num_kv_heads, head_dim)
    seq_axis = "kv_long" if long_context else "kv_seq"
    spec = ("dp_batch" if not long_context else None, seq_axis, None, None)
    return jax.ShapeDtypeStruct(shape, dtype), spec


def apply_attention_decode(
    p: dict,
    x: jax.Array,  # (b, 1, d) current-token activations
    cache: dict,  # {'k','v'}: (b, T, nkv, hd)
    index: jax.Array,  # scalar int32 — write position (same for batch)
    *,
    positions: jax.Array,  # (b, 1) or (b, 1, 3)
    rope_type: str,
    rope_theta: float,
    mrope_sections=(),
    qk_norm: bool = False,
    mask_kind: str = "causal",
    window: int = 0,
    attn_softcap: float = 0.0,
    long_context: bool = False,
    norm_eps: float = 1e-6,
):
    q, k, v = _project_qkv(p, x, x, qk_norm=qk_norm, norm_eps=norm_eps)
    q = rope_lib.apply_positional(q, positions, rope_type, rope_theta, mrope_sections)
    k = rope_lib.apply_positional(k, positions, rope_type, rope_theta, mrope_sections)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), index, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), index, axis=1)
    seq_axis = "kv_long" if long_context else "kv_seq"
    batch_axis = None if long_context else "dp_batch"
    ck = shd(ck, batch_axis, seq_axis, None, None)
    cv = shd(cv, batch_axis, seq_axis, None, None)
    T = ck.shape[1]
    kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (x.shape[0], T))
    valid = kpos <= index
    # Mask position of the query is its cache slot, not its rope id.
    qpos = jnp.broadcast_to(index.astype(jnp.int32), (x.shape[0], 1))
    out = attend_ref(
        q, ck, cv,
        mask_kind="window" if mask_kind == "window" else "full",
        window=window, attn_softcap=attn_softcap,
        qpos=qpos, kpos=kpos, kv_valid=valid,
        kv_seq_axis=seq_axis if os.environ.get("REPRO_DECODE_SHARDED", "1") == "1" else None,
    )
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": ck, "v": cv}
