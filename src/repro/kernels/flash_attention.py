"""Pallas TPU flash attention, VMEM-tiled online softmax.

TPU-native adaptation (DESIGN.md §8): q tiles of BLOCK_Q=256 rows stream
through VMEM while the kv reduction runs along the innermost grid axis;
(m, l, acc) online-softmax carries live in VMEM scratch across kv steps.
The kernel works head-major — ``(b, nh, s, hd)`` with ``(bq, hd)`` tiles,
so a tile's last two dims are a sequence slice (a multiple of 8, or the
whole sequence) and the whole head dim — and the public wrapper keeps
the model's ``(b, s, nh, hd)`` layout.  Supports causal masking, sliding
windows (gemma2 local layers), GQA head grouping via BlockSpec index
maps, and tanh soft-capping — fused, so the masked QK^T logits never
round-trip to HBM.

The backward pass is the oracle's (``kernels/ref.py``) through
``jax.vjp``, attached with ``jax.custom_vjp``: ``jax.grad`` of a train
step differentiates the same math the kernel computes forward.  It
materialises the float32 scores, so where they would pass
``SCORE_BYTES_MAX`` it runs over chunks of batch rows, one after another.

Validated against kernels/ref.py in interpret mode (CPU) by
tests/test_kernels.py; selected automatically on TPU by kernels/ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as ref_lib

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
NEG_INF = -2.0e38
#: the largest float32 score tensor (rows x heads x queries x keys) the
#: backward pass materialises at once
SCORE_BYTES_MAX = 3 << 29  # 1.5 GiB


def _attn_kernel(
    q_ref,  # (bq, hd)
    k_ref,  # (bk, hd)
    v_ref,  # (bk, hd)
    o_ref,  # (bq, hd)
    m_scr,  # (bq, 1) f32  running max
    l_scr,  # (bq, 1) f32  running denom
    acc_scr,  # (bq, hd) f32  running numerator
    *,
    mask_kind: str,
    window: int,
    attn_softcap: float,
    block_q: int,
    block_k: int,
    n_k_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    hd = q.shape[-1]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (hd ** -0.5)  # (bq, bk)
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    if mask_kind != "full":
        ok = kpos <= qpos
        if mask_kind == "window" and window > 0:
            ok &= (qpos - kpos) < window
        s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)  # (bq, bk)
    l_cur = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_cur
    l_scr[...] = l_cur

    @pl.when(ki == n_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _forward(q, k, v, mask_kind, window, attn_softcap, block_q, block_k,
             interpret):
    """The kernel on head-major operands: q (b, nh, s, hd), k/v
    (b, nkv, t, hd) -> (b, nh, s, hd)."""
    b, nh, s, hd = q.shape
    nkv, t = k.shape[1], k.shape[2]
    group = nh // nkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    n_q = s // block_q
    n_k = t // block_k

    kernel = functools.partial(
        _attn_kernel,
        mask_kind=mask_kind, window=window, attn_softcap=attn_softcap,
        block_q=block_q, block_k=block_k, n_k_blocks=n_k,
    )
    # batch and head dims squeezed: the kernel sees (bq, hd) / (bk, hd)
    q_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, hd),
                           lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, nh, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, s, hd), q.dtype),
        scratch_shapes=[
            # (bq, 1) m, (bq, 1) l, (bq, hd) acc — f32 online-softmax carries
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        # the trace names the kernel's operation after this name, and the
        # benchmark's flash_attn_roofline finds it by it
        name="flash_attention_pallas",
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention(q, k, v, mask_kind, window, attn_softcap, block_q, block_k,
               interpret):
    """(b, s, nh, hd) in and out; the kernel runs head-major."""
    out = _forward(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                   v.transpose(0, 2, 1, 3), mask_kind, window, attn_softcap,
                   block_q, block_k, interpret)
    return out.transpose(0, 2, 1, 3)


def _attention_fwd(q, k, v, mask_kind, window, attn_softcap, block_q,
                   block_k, interpret):
    out = _attention(q, k, v, mask_kind, window, attn_softcap, block_q,
                     block_k, interpret)
    return out, (q, k, v)


def bwd_rows(b: int, nh: int, s: int, t: int) -> int:
    """Batch rows per chunk of the backward pass: the most rows, dividing
    ``b``, whose scores fit in ``SCORE_BYTES_MAX`` (at least one)."""
    per_row = 4 * nh * s * t
    return max(c for c in range(1, b + 1)
               if b % c == 0 and (c == 1 or c * per_row <= SCORE_BYTES_MAX))


def _attention_bwd(mask_kind, window, attn_softcap, block_q, block_k,
                   interpret, res, g):
    ref = functools.partial(ref_lib.flash_attention_ref, mask_kind=mask_kind,
                            window=window, attn_softcap=attn_softcap)

    def vjp_of(q, k, v, g):
        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g)

    q, k, v = res
    b = q.shape[0]
    rows = bwd_rows(b, q.shape[2], q.shape[1], k.shape[1])
    if rows == b:
        return vjp_of(q, k, v, g)
    chunks = [x.reshape(b // rows, rows, *x.shape[1:]) for x in (q, k, v, g)]
    grads = jax.lax.map(lambda a: vjp_of(*a), tuple(chunks))
    return tuple(x.reshape(b, *x.shape[2:]) for x in grads)


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mask_kind", "window", "attn_softcap", "block_q", "block_k", "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # (b, s, nh, hd)
    k: jax.Array,  # (b, t, nkv, hd)
    v: jax.Array,
    *,
    mask_kind: str = "causal",
    window: int = 0,
    attn_softcap: float = 0.0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    return _attention(q, k, v, mask_kind, window, attn_softcap, block_q,
                      block_k, interpret)
