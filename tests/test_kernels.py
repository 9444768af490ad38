"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode
executes the Pallas kernel body on CPU, per the assignment)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # clean environments: deterministic sweeps still run
    HAS_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import dequantize_int8_pallas, quantize_int8_pallas


def _qkv(key, b, s, t, nh, nkv, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, nh, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, nkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, nkv, hd), jnp.float32).astype(dtype)
    return q, k, v


SWEEP = [
    # (s, t, nh, nkv, hd, mask, window, softcap, dtype, tol)
    (128, 128, 4, 4, 64, "causal", 0, 0.0, jnp.float32, 2e-6),
    (256, 256, 4, 2, 64, "causal", 0, 0.0, jnp.float32, 2e-6),
    (256, 256, 8, 1, 128, "causal", 0, 0.0, jnp.float32, 2e-6),
    (512, 512, 4, 2, 128, "window", 128, 0.0, jnp.float32, 2e-6),
    (256, 256, 2, 2, 256, "window", 4096, 0.0, jnp.float32, 2e-6),  # win > seq
    (128, 128, 4, 4, 64, "full", 0, 0.0, jnp.float32, 2e-6),
    (256, 256, 8, 4, 64, "causal", 0, 50.0, jnp.float32, 2e-6),  # gemma softcap
    (256, 256, 4, 4, 128, "causal", 0, 0.0, jnp.bfloat16, 2e-2),
    (512, 512, 6, 6, 64, "window", 256, 30.0, jnp.bfloat16, 2e-2),
]


@pytest.mark.parametrize("s,t,nh,nkv,hd,mask,win,cap,dtype,tol", SWEEP)
def test_flash_attention_matches_oracle(s, t, nh, nkv, hd, mask, win, cap, dtype, tol):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, s, t, nh, nkv, hd, dtype)
    got = flash_attention_pallas(
        q, k, v, mask_kind=mask, window=win, attn_softcap=cap, interpret=True
    )
    want = ref.flash_attention_ref(q, k, v, mask_kind=mask, window=win, attn_softcap=cap)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_block_shapes():
    """Non-default BlockSpec tilings stay correct."""
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 512, 512, 4, 2, 64, jnp.float32)
    want = ref.flash_attention_ref(q, k, v, mask_kind="causal")
    for bq, bk in [(128, 128), (256, 512), (512, 256)]:
        got = flash_attention_pallas(
            q, k, v, mask_kind="causal", block_q=bq, block_k=bk, interpret=True
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=2e-6)


GRAD_SWEEP = [
    # (s, t, nh, nkv, hd, mask, window, softcap, block_q, block_k, dtype, tol)
    pytest.param(128, 128, 4, 4, 64, "causal", 0, 0.0, 64, 64, jnp.float32,
                 1e-5, id="causal-0-0.0-4"),
    pytest.param(128, 128, 4, 2, 64, "window", 64, 30.0, 64, 64, jnp.float32,
                 1e-5, id="window-64-30.0-2"),
    pytest.param(128, 128, 4, 1, 64, "full", 0, 0.0, 64, 64, jnp.float32,
                 1e-5, id="full-0-0.0-1"),
    # several query and kv tiles, not the same size, groups of 3 and 4
    pytest.param(256, 256, 6, 2, 64, "causal", 0, 0.0, 64, 128, jnp.float32,
                 1e-5, id="causal-group3-bq64-bk128"),
    pytest.param(256, 256, 6, 2, 64, "causal", 0, 50.0, 128, 32,
                 jnp.float32, 1e-5, id="causal-cap50-group3-bq128-bk32"),
    pytest.param(256, 256, 8, 2, 32, "window", 40, 0.0, 32, 64, jnp.float32,
                 1e-5, id="window40-group4-bq32-bk64"),
    pytest.param(128, 128, 3, 3, 64, "window", 4096, 0.0, 32, 64,
                 jnp.float32, 1e-5, id="window-beyond-seq-group1"),
    pytest.param(128, 128, 6, 2, 32, "full", 0, 30.0, 64, 32, jnp.float32,
                 1e-5, id="full-cap30-group3-bq64-bk32"),
    # t unequal to s
    pytest.param(64, 128, 8, 2, 32, "causal", 0, 0.0, 32, 32, jnp.float32,
                 1e-5, id="causal-s64-t128-group4"),
    pytest.param(128, 64, 6, 2, 32, "full", 0, 0.0, 32, 32, jnp.float32,
                 1e-5, id="full-s128-t64-group3"),
    pytest.param(256, 256, 6, 2, 64, "causal", 0, 0.0, 64, 128,
                 jnp.bfloat16, 2e-2, id="bf16-causal-group3"),
    pytest.param(128, 128, 8, 2, 32, "window", 48, 30.0, 32, 64,
                 jnp.bfloat16, 2e-2, id="bf16-window48-cap30-group4"),
]


@pytest.mark.parametrize("s,t,nh,nkv,hd,mask,win,cap,bq,bk,dtype,tol",
                         GRAD_SWEEP)
def test_flash_attention_grad_matches_oracle(s, t, nh, nkv, hd, mask, win,
                                             cap, bq, bk, dtype, tol):
    """jax.grad through the kernel (its custom_vjp: the Pallas dk/dv and
    dq kernels) equals jax.grad of the oracle, for q, k and v."""
    q, k, v = _qkv(jax.random.PRNGKey(4), 2, s, t, nh, nkv, hd, dtype)
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(
            q, k, v, mask_kind=mask, window=win, attn_softcap=cap
        ).astype(jnp.float32) * w)

    pallas = functools.partial(flash_attention_pallas, block_q=bq,
                               block_k=bk, interpret=True)
    got = jax.grad(loss(pallas), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref.flash_attention_ref), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.dtype == r.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=tol, rtol=tol)


def _kernel_calls(jaxpr):
    """(name, number of outputs) of every Pallas call in a jaxpr, nested
    ones included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], len(eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_kernel_calls(sub))
    return out


def test_primal_call_runs_the_single_output_forward():
    """Without a gradient the forward kernel writes the output alone; the
    log-sum-exp output and the backward kernels appear only under grad."""
    q, k, v = _qkv(jax.random.PRNGKey(6), 1, 128, 128, 4, 2, 32, jnp.float32)
    fwd = functools.partial(flash_attention_pallas, block_q=64, block_k=64,
                            interpret=True)
    primal = jax.make_jaxpr(fwd)(q, k, v)
    assert _kernel_calls(primal.jaxpr) == [("flash_attention_pallas", 1)]
    grad = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fwd(q, k, v)),
                                   (0, 1, 2)))(q, k, v)
    assert sorted(_kernel_calls(grad.jaxpr)) == [
        ("flash_attention_bwd_dkv", 2), ("flash_attention_bwd_dq", 1),
        ("flash_attention_pallas", 2)]


def test_ops_dispatch_ref_on_cpu():
    """On this CPU container the default impl must be the oracle itself."""
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 64, 64, 2, 2, 32, jnp.float32)
    got = ops.flash_attention(q, k, v, mask_kind="causal")
    want = ref.flash_attention_ref(q, k, v, mask_kind="causal")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0, rtol=0)


@pytest.mark.parametrize("n,dtype", [
    (256 * 64, jnp.float32),
    (256 * 64 * 4, jnp.float32),
    (256 * 128, jnp.bfloat16),
    (256 * 100, jnp.float32),  # rows not a whole number of row tiles
    (256 * 5, jnp.float32),    # fewer rows than one tile
])
def test_quantize_matches_oracle(n, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32) * 3).astype(dtype)
    q_p, s_p = quantize_int8_pallas(x, interpret=True)
    q_r, s_r = ref.quantize_int8_ref(x)
    assert (np.asarray(q_p) == np.asarray(q_r)).mean() > 0.999  # rounding ties
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r), rtol=1e-6)
    # dequant kernels must agree exactly on identical inputs
    x_p = dequantize_int8_pallas(q_r, s_r, interpret=True)
    x_r = ref.dequantize_int8_ref(q_r, s_r)
    np.testing.assert_allclose(np.asarray(x_p), np.asarray(x_r), atol=1e-6)


if HAS_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 64), st.floats(0.01, 100.0))
    def test_quantize_error_bound(blocks, scale_mag):
        """|x - dq(q(x))| <= amax/254 per block — the int8 quantization error
        bound that makes checkpoint compression training-safe."""
        n = 256 * blocks
        x = jax.random.normal(jax.random.PRNGKey(blocks), (n,), jnp.float32) * scale_mag
        q, s = ref.quantize_int8_ref(x)
        xd = ref.dequantize_int8_ref(q, s)
        err = np.abs(np.asarray(xd - x)).reshape(blocks, 256)
        amax = np.abs(np.asarray(x)).reshape(blocks, 256).max(axis=1)
        bound = amax / 254 + 1e-7
        assert (err.max(axis=1) <= bound + 1e-6 * amax).all()
else:
    @pytest.mark.skip(reason="hypothesis not installed; property tests inactive")
    def test_quantize_error_bound():
        pass


def test_quantize_zero_block():
    x = jnp.zeros((512,), jnp.float32)
    q, s = ref.quantize_int8_ref(x)
    assert (np.asarray(q) == 0).all()
    xd = ref.dequantize_int8_ref(q, s)
    assert (np.asarray(xd) == 0).all()
