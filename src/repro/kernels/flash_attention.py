"""Pallas TPU flash attention, VMEM-tiled online softmax, forward and
backward.

TPU-native adaptation (DESIGN.md §8): q tiles of BLOCK_Q=256 rows stream
through VMEM while the kv reduction runs along the innermost grid axis;
(m, l, acc) online-softmax carries live in VMEM scratch across kv steps.
The kernels work head-major — ``(b, nh, s, hd)`` with ``(bq, hd)`` tiles,
so a tile's last two dims are a sequence slice (a multiple of 8, or the
whole sequence) and the whole head dim — and the public wrapper keeps
the model's ``(b, s, nh, hd)`` layout.  Supports causal masking, sliding
windows (gemma2 local layers), GQA head grouping via BlockSpec index
maps, and tanh soft-capping — fused, so the masked QK^T logits never
round-trip to HBM.

The backward pass (FlashAttention-2 style, attached with
``jax.custom_vjp``) is two more kernels fed by the forward's per-row
log-sum-exp ``lse = m + log(l)``: ``flash_attention_bwd_dkv`` holds one
kv tile and reduces over the query tiles of every query head of its
group (GQA summed through the index maps), ``flash_attention_bwd_dq``
holds one query tile and reduces over the kv tiles.  Both recompute the
scores tile by tile, ``p = exp(s - lse)``, ``dS = p * (dO v^T - delta)``
with ``delta = rowsum(dO * out)``, so no score, probability or
score-gradient tensor larger than one tile reaches HBM.  Tiles that the
mask empties are skipped: no compute, and their index maps repeat the
previous block so no copy is issued.

Validated against kernels/ref.py in interpret mode (CPU) by
tests/test_kernels.py; selected automatically on TPU by kernels/ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
NEG_INF = -2.0e38
LANES = 128
# contract the last dims (a @ b.T) and the inner dims (a @ b)
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims):
    """A tile product on the operands' own dtypes (the wider where they
    differ), accumulated in float32 at default precision."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(a.astype(dt), b.astype(dt), dims,
                               preferred_element_type=jnp.float32)


def _mask(s, qpos, kpos, mask_kind, window):
    """The forward's mask on a tile of scores."""
    if mask_kind == "full":
        return s
    ok = kpos <= qpos
    if mask_kind == "window" and window > 0:
        ok &= (qpos - kpos) < window
    return jnp.where(ok, s, NEG_INF)


def _column_to_row(col):
    """An (n, 1) column as a (1, n) row, through a lane-aligned transpose."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], LANES)))[:1]


def _row_to_column(row):
    """A (1, n) row as an (n, 1) column, through a lane-aligned transpose."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))[:, :1]


def _attn_kernel(
    q_ref,  # (bq, hd)
    k_ref,  # (bk, hd)
    v_ref,  # (bk, hd)
    o_ref,  # (bq, hd)
    *refs,  # [lse (1, bq) f32,] then the scratch below
    with_lse: bool,
    mask_kind: str,
    window: int,
    attn_softcap: float,
    block_q: int,
    block_k: int,
    n_k_blocks: int,
):
    lse_ref = refs[0] if with_lse else None
    m_scr, l_scr, acc_scr = refs[1:] if with_lse else refs
    # m_scr (bq, 1) f32 running max, l_scr (bq, 1) f32 running denom,
    # acc_scr (bq, hd) f32 running numerator
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
    s = _mask(s, qpos, kpos, mask_kind, window)

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
        if with_lse:
            lse_ref[...] = _column_to_row(m_scr[...] + jnp.log(denom))


def _forward(q, k, v, mask_kind, window, attn_softcap, block_q, block_k,
             interpret, with_lse):
    """The kernel on head-major operands: q (b, nh, s, hd), k/v
    (b, nkv, t, hd) -> (b, nh, s, hd), and with ``with_lse`` also the
    float32 log-sum-exp of every query row as (b, nh, 1, s)."""
    b, nh, s, hd = q.shape
    nkv, t = k.shape[1], k.shape[2]
    group = nh // nkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    n_q = s // block_q
    n_k = t // block_k

    kernel = functools.partial(
        _attn_kernel, with_lse=with_lse,
        mask_kind=mask_kind, window=window, attn_softcap=attn_softcap,
        block_q=block_q, block_k=block_k, n_k_blocks=n_k,
    )
    # batch and head dims squeezed: the kernel sees (bq, hd) / (bk, hd)
    q_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, hd),
                           lambda bi, hi, qi, ki: (bi, hi // group, ki, 0))
    out_specs = q_spec
    out_shape = jax.ShapeDtypeStruct((b, nh, s, hd), q.dtype)
    if with_lse:
        out_specs = [q_spec, pl.BlockSpec(
            (None, None, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, nh, 1, s), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(b, nh, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
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


def _q_blocks(ki, mask_kind, window, block_q, block_k, n_q):
    """The first and last query tile that kv tile ``ki`` is visible
    from (first > last when none is)."""
    if mask_kind == "full":
        return 0, n_q - 1
    last = n_q - 1
    if mask_kind == "window" and window > 0:
        last = jnp.minimum(last, (ki * block_k + block_k + window - 2) // block_q)
    return ki * block_k // block_q, last


def _k_blocks(qi, mask_kind, window, block_q, block_k, n_k):
    """The first and last kv tile that query tile ``qi`` sees."""
    if mask_kind == "full":
        return 0, n_k - 1
    first = 0
    if mask_kind == "window" and window > 0:
        first = jnp.maximum(qi * block_q - window + 1, 0) // block_k
    return first, jnp.minimum(n_k - 1, (qi * block_q + block_q - 1) // block_k)


def _tile_grads(x, y, dx, dy, lse, delta, qpos, kpos, mask_kind, window,
                attn_softcap):
    """p and dS, the gradient of the (scaled) scores, on one tile whose
    scores are ``x @ y.T`` and whose ``dP`` is ``dx @ dy.T``: the query
    side is (x, dx) = (q, dO) with ``lse`` and ``delta`` as columns, or
    the key side is (x, dx) = (k, v), the tile transposed, with them as
    rows."""
    s = _dot(x, y, _NT) * (x.shape[-1] ** -0.5)
    if attn_softcap:
        th = jnp.tanh(s / attn_softcap)
        s = attn_softcap * th
    p = jnp.exp(_mask(s, qpos, kpos, mask_kind, window) - lse)
    ds = p * (_dot(dx, dy, _NT) - delta)
    if attn_softcap:
        ds = ds * (1.0 - th * th)
    return p, ds


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, mask_kind, window,
                attn_softcap, block_q, block_k, n_q, n_steps):
    ki = pl.program_id(2)
    j = pl.program_id(3)  # (query head of the group, query tile)
    qi = j % n_q

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    first, last = _q_blocks(ki, mask_kind, window, block_q, block_k, n_q)

    @pl.when((qi >= first) & (qi <= last))
    def _accumulate():
        q, do = q_ref[...], do_ref[...]
        shape = (block_k, block_q)  # the tile transposed: kv rows
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        pT, dsT = _tile_grads(k_ref[...], q, v_ref[...], do, lse_ref[...],
                              delta_ref[...], qpos, kpos, mask_kind, window,
                              attn_softcap)
        dv_scr[...] += _dot(pT, do, _NN)
        dk_scr[...] += _dot(dsT, q, _NN)

    @pl.when(j == n_steps - 1)
    def _finalize():
        scale = q_ref.shape[-1] ** -0.5
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, lse_scr, delta_scr, *, mask_kind, window,
               attn_softcap, block_q, block_k, n_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = _row_to_column(lse_ref[...])
        delta_scr[...] = _row_to_column(delta_ref[...])

    first, last = _k_blocks(qi, mask_kind, window, block_q, block_k, n_k)

    @pl.when((ki >= first) & (ki <= last))
    def _accumulate():
        k = k_ref[...]
        shape = (block_q, block_k)
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        _, ds = _tile_grads(q_ref[...], k, do_ref[...], v_ref[...],
                            lse_scr[...], delta_scr[...], qpos, kpos,
                            mask_kind, window, attn_softcap)
        dq_scr[...] += _dot(ds, k, _NN)

    @pl.when(ki == n_k - 1)
    def _finalize():
        scale = q_ref.shape[-1] ** -0.5
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _backward(q, k, v, do, lse, delta, mask_kind, window, attn_softcap,
              block_q, block_k, interpret):
    """dq, dk, dv on head-major operands, from the forward's ``lse`` and
    ``delta = rowsum(dO * out)``, both (b, nh, 1, s) float32."""
    b, nh, s, hd = q.shape
    nkv, t = k.shape[1], k.shape[2]
    group = nh // nkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    n_q, n_k = s // block_q, t // block_k
    static = dict(mask_kind=mask_kind, window=window,
                  attn_softcap=attn_softcap, block_q=block_q, block_k=block_k)

    # dk, dv: grid (batch, kv head, kv tile, (query head of the group,
    # query tile)); a skipped query tile maps to the nearest visible one,
    # so its step repeats a block already there
    def q_tile(bi, hk, ki, j):
        first, last = _q_blocks(ki, mask_kind, window, block_q, block_k, n_q)
        return bi, hk * group + j // n_q, jnp.minimum(
            jnp.maximum(j % n_q, first), last)

    def q_map(bi, hk, ki, j):
        return (*q_tile(bi, hk, ki, j), 0)

    def row_map(bi, hk, ki, j):
        bi, hi, qi = q_tile(bi, hk, ki, j)
        return bi, hi, 0, qi

    q_spec = pl.BlockSpec((None, None, block_q, hd), q_map)
    row_spec = pl.BlockSpec((None, None, 1, block_q), row_map)
    kv_spec = pl.BlockSpec((None, None, block_k, hd),
                           lambda bi, hk, ki, j: (bi, hk, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, n_steps=group * n_q,
                          **static),
        grid=(b, nkv, n_k, group * n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)

    # dq: grid (batch, head, query tile, kv tile); a skipped kv tile maps
    # to the nearest visible one
    def kv_map(bi, hi, qi, ki):
        first, last = _k_blocks(qi, mask_kind, window, block_q, block_k, n_k)
        return bi, hi // group, jnp.minimum(jnp.maximum(ki, first), last), 0

    q_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    row_spec = pl.BlockSpec((None, None, 1, block_q),
                            lambda bi, hi, qi, ki: (bi, hi, 0, qi))
    kv_spec = pl.BlockSpec((None, None, block_k, hd), kv_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k=n_k, **static),
        grid=(b, nh, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _head_major(x):
    return x.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention(q, k, v, mask_kind, window, attn_softcap, block_q, block_k,
               interpret):
    """(b, s, nh, hd) in and out; the kernel runs head-major."""
    out = _forward(_head_major(q), _head_major(k), _head_major(v), mask_kind,
                   window, attn_softcap, block_q, block_k, interpret,
                   with_lse=False)
    return _head_major(out)


def _attention_fwd(q, k, v, mask_kind, window, attn_softcap, block_q,
                   block_k, interpret):
    q, k, v = _head_major(q), _head_major(k), _head_major(v)
    out, lse = _forward(q, k, v, mask_kind, window, attn_softcap, block_q,
                        block_k, interpret, with_lse=True)
    return _head_major(out), (q, k, v, out, lse)


def _attention_bwd(mask_kind, window, attn_softcap, block_q, block_k,
                   interpret, res, g):
    q, k, v, out, lse = res
    do = _head_major(g)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    grads = _backward(q, k, v, do, lse, delta, mask_kind, window,
                      attn_softcap, block_q, block_k, interpret)
    return tuple(_head_major(x) for x in grads)


_attention.defvjp(_attention_fwd, _attention_bwd)


def _flash_attention(
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


# XLA names the kernels' results after the jitted function, and the
# trace writes those names into every operation that reads them: so only
# the forward kernel itself may carry "flash_attention_pallas", which the
# benchmark's flash_attn_roofline matches
flash_attention_pallas = jax.jit(
    _flash_attention,
    static_argnames=("mask_kind", "window", "attn_softcap", "block_q",
                     "block_k", "interpret"))
