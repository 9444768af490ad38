"""The main path's Pallas kernels compiled for a described TPU v5e chip.

Nothing runs: the TPU compiler is handed shapes and refuses here what it
would refuse on the chip (a block shape off the (8, 128) tiling, too much
VMEM).  Every compile must keep the kernel as a Mosaic custom call
(``tpu_custom_call``), i.e. compiled, not interpreted.  The topology is
described inside a fixture, so only the test worker that runs this file
loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import policy_kernels as pk
from repro.core.orchestrator import FeasibilityAwarePolicy
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import (
    ROWS, dequantize_int8_pallas, quantize_int8_pallas,
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, one_chip, *shapes, **kw):
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    return fn.lower(*args, **kw).compile().as_text()


def _batch(B, K, S):
    """A padded decide batch of the given bucket shape (values are
    irrelevant to the compile)."""
    z = np.zeros((B, K))
    return pk.ScoreBatch(
        sizes=np.ones((B, K)), t_loads=z, rem=z, cur_green=z, load_src=z,
        s_i=np.zeros((B, K), np.int32), bw=np.ones((B, K, S)),
        W=np.zeros((B, S)), bq_load=np.zeros((B, S)),
        free_slots=np.ones((B, S), np.int64), n_jobs=(K,) * B,
        n_sites=(S,) * B)


@pytest.mark.parametrize("B,K,S", [
    (1, 16384, 128),  # one fleet cell at the largest job bucket
    (8, 64, 128),     # a batched sweep round
])
def test_decide_kernel_compiles(one_chip, B, K, S):
    key, args = pk._pallas_inputs(_batch(B, K, S),
                                  FeasibilityAwarePolicy()._params())
    hlo = _hlo(pk._pallas_fn(*key, False), one_chip, *args)
    assert "tpu_custom_call" in hlo
    # the stable name the trace shows, on the int32 result the benchmark's
    # decide_kernel_roofline reads
    assert f"%_dest_kernel.1 = s32[{B},1,{K}]" in hlo


def _attention_shapes(batch=8, seq=256):
    cfg = get_config("micro-lm-100m")
    hd, dt = cfg.resolved_head_dim, jnp.dtype(cfg.dtype)
    q = jax.ShapeDtypeStruct((batch, seq, cfg.num_heads, hd), dt)
    kv = jax.ShapeDtypeStruct((batch, seq, cfg.num_kv_heads, hd), dt)
    return q, kv, kv


@pytest.mark.parametrize("pass_", ["forward", "grad"])
def test_flash_attention_compiles_at_micro_lm_100m_widths(one_chip, pass_):
    def fwd(q, k, v):
        return flash_attention_pallas(q, k, v, mask_kind="causal")

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v))

    fn = fwd if pass_ == "forward" else jax.value_and_grad(loss, (0, 1, 2))
    hlo = _hlo(jax.jit(fn), one_chip, *_attention_shapes())
    assert "tpu_custom_call" in hlo
    # the benchmark's flash_attn_roofline finds the kernel by this name
    assert "%flash_attention_pallas." in hlo


@pytest.mark.parametrize("kernel", ["quantize", "dequantize"])
def test_int8_kernels_compile_at_ragged_row_count(one_chip, kernel):
    rows = 3 * ROWS + 37  # not a whole number of row tiles
    n = rows * 256
    if kernel == "quantize":
        shapes = (jax.ShapeDtypeStruct((n,), jnp.float32),)
        fn = quantize_int8_pallas
    else:
        shapes = (jax.ShapeDtypeStruct((n,), jnp.int8),
                  jax.ShapeDtypeStruct((rows,), jnp.float32))
        fn = dequantize_int8_pallas
    hlo = _hlo(fn, one_chip, *shapes)
    assert "tpu_custom_call" in hlo
    assert {"quantize": "%_quant_kernel.", "dequantize": "%_dequant_kernel."}[
        kernel] in hlo


def test_expert_layer_and_conv_compile_at_lfm2_widths(one_chip):
    """The dropless expert layer (grouped products over lfm2-moe-train's
    worst-case 65,536-row buffer, 8 of 32 experts held) and the conv
    block compile for the chip, forward and backward, and their named
    scopes are in the ``op_name`` of the compiled operations, which is
    what the trace's operations carry and what the benchmark's expert
    readers select by."""
    from repro.models import conv as conv_lib
    from repro.models import moe as moe_lib

    d, f, E, R = 2048, 1792, 8, 32
    p = jax.eval_shape(lambda k: {
        "moe": moe_lib.init_moe(k, d, f, E, 4, jnp.float32, router_experts=R,
                                router_bias=True),
        "conv": conv_lib.init_conv(k, d, 3, 4, jnp.float32)},
        jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((8, 2048, d), jnp.float32)

    def loss(p, x):
        y = conv_lib.apply_conv(p["conv"], x)
        y, _, _ = moe_lib.apply_moe_dropless(
            p["moe"], y, top_k=4, act="silu", score="sigmoid")
        return jnp.sum(y)

    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    hlo = jax.jit(jax.grad(loss)).lower(
        jax.tree.map(on_chip, p), on_chip(x)).compile().as_text()
    assert "ragged-dot" in hlo  # grouped products, not dense ones
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("moe.route", "moe.experts", "moe.combine", "conv"):
        # forward, and the backward's operations under ``transpose(...)``
        for kind in ("jvp(", "transpose("):
            assert any(kind in n and re.search(
                r"(^|[/(])" + re.escape(scope) + r"($|[/)])", n)
                for n in names), (scope, kind)
