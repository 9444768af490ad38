"""LFM2-MoE on the CPU at small widths: the program (conv blocks, the
dropless expert-share layer, per-block remat, the attention backward at
lfm2's grouping) against the plain reference
``bench/configs/lfm2_moe_ref.py`` on seeded random weights, and the
published model's size."""
import functools
import importlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, param_count
from repro.configs.base import ModelConfig, active_param_count
from repro.kernels import ref as kernel_ref
from repro.models import build_model
from repro.models import conv as conv_lib
from repro.models import moe as moe_lib

# the module, not the function the package exports under its name
fa = importlib.import_module("repro.kernels.flash_attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("lfm2_moe_ref", "bench/configs/lfm2_moe_ref.py")
#: the benchmark's configuration at small widths: a dense conv layer, then
#: attention and conv layers with 4 of 8 experts held (experts 4-7)
C = {**json.load(open(os.path.join(ROOT, "bench/configs/lfm2-8b-a1b.json"))),
     "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
     "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
     "num_hidden_layers": 4, "num_dense_layers": 1, "num_experts": 4,
     "router_experts": 8, "first_expert": 4, "num_experts_per_tok": 2,
     "vocab_size": 128, "layer_types": ["conv", "full_attention", "conv", "conv"]}


def program_config(c, **kw):
    L = c["num_hidden_layers"]
    return ModelConfig(
        name="lfm2-small", family="hybrid", num_layers=L, d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], qk_norm=True,
        block_pattern=tuple(REF.layer_kinds(c)),
        moe_pattern=tuple(range(c["num_dense_layers"], L)), moe=True,
        num_experts=c["num_experts"], router_experts=c["router_experts"],
        first_expert=c["first_expert"], top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"], router_aux_coef=0.0,
        router_score="sigmoid", router_bias=True, moe_impl="dropless",
        conv_kernel=c["conv_L_cache"], rms_norm_eps=c["norm_eps"],
        tie_embeddings=True, dtype="float32", **kw)


def batch(c, b=2, s=32, seed=3):
    toks = np.random.default_rng(seed).integers(0, c["vocab_size"], (b, s + 1))
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32)}


def moe_params(c, seed=0):
    """One MoE layer's weights of the reference, without the group axis."""
    p = REF.init_params(jax.random.PRNGKey(seed), c)["groups"]
    return jax.tree.map(lambda w: w[0], p[f"b{c['num_dense_layers']}"]["moe"])


def ref_moe(c, h, p):
    ein = functools.partial(jnp.einsum, precision=REF.HIGHEST)
    return REF._moe(c, ein, h, p)


def share(c, h, p, first):
    """The program's dropless layer holding the experts of ``p``, the
    first of them expert ``first``."""
    return moe_lib.apply_moe_dropless(
        p, h, top_k=c["num_experts_per_tok"], act="silu", score="sigmoid",
        norm_topk=True, scale=1.0, first=first)


# -- the cases -----------------------------------------------------------------

def loss_and_grad_match_reference():
    # float32 on the CPU: only the order of sums differs (rtol 1e-4 is
    # ~100x the float32 rounding of a 64-wide dot product; a dropped row
    # or another expert's weights changes the loss at the 1e-2 level)
    model = build_model(program_config(C))
    params = REF.init_params(jax.random.PRNGKey(1), C)
    bt = batch(C)
    (loss, met), g = jax.value_and_grad(model.loss, has_aux=True)(params, bt)
    want_sum, want_g = jax.value_and_grad(REF.loss_sum)(
        params, bt["tokens"], bt["labels"], C)
    n = bt["tokens"].size
    assert float(loss) == pytest.approx(float(want_sum) / n, rel=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b / n, rtol=1e-4, atol=1e-7)
    held = int(met["moe_rows_held"])
    pick = np.asarray(REF.choices(params, bt["tokens"], C))[..., 4:8]
    assert held == int(pick.sum())  # every routed row of the held experts


def shares_add_up_to_the_uncut_layer():
    c = {**C, "num_experts": 8, "first_expert": 0}
    p = moe_params(c)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 32, c["hidden_size"]))
    whole = ref_moe(c, h, p)
    parts = [share(c, h, {**p, **{k: p[k][2 * r:2 * r + 2] for k in ("wi", "wg", "wo")}},
                   2 * r)[0] for r in range(4)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-6)


def one_expert_batch_matches_reference():
    """Every token chooses held expert 4 (and one other): the dropless
    layer computes all of its rows; capacity 1.5 drops most of them."""
    p = moe_params(C)
    bias = jnp.zeros(8).at[4].set(100.0).at[0].set(50.0)
    p = {**p, "expert_bias": bias}
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 256, C["hidden_size"]))
    want = ref_moe(C, h, p)
    got, _, rows = share(C, h, p, 4)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    assert int(rows["held"]) == int(rows["max"]) == 2 * 256
    gates = moe_lib.share_gates(p, h, top_k=2, score="sigmoid", norm_topk=True,
                                scale=1.0, first=4)
    dropped, _ = moe_lib.apply_moe_capacity(p, h, top_k=2, act="silu", gates=gates,
                                            num_router=8)
    lost = np.linalg.norm(dropped - want) / np.linalg.norm(want)
    assert lost > 0.1, lost


def conv_is_causal():
    d = 8
    p = conv_lib.init_conv(jax.random.PRNGKey(5), d, 3, 4, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 12, d))
    y = conv_lib.apply_conv(p, x)
    x2 = x.at[:, 7].add(1.0)
    y2 = conv_lib.apply_conv(p, x2)
    np.testing.assert_array_equal(y[:, :7], y2[:, :7])
    assert not np.allclose(y[:, 7], y2[:, 7])
    # out[t] = sum_j w[j] u[t - 2 + j], with u = B * x
    u = jnp.arange(5.0)[None, :, None] * jnp.ones((1, 1, d))
    w = jnp.ones((3, d)) * jnp.array([1.0, 10.0, 100.0])[:, None]
    got = conv_lib.causal_depthwise_conv(u, w)[0, :, 0]
    np.testing.assert_allclose(got, [0, 100, 210, 321, 432])


def kernel_attention_backward_matches_at_lfm2_grouping():
    """The Pallas backward at lfm2's grouping, 4 query heads a KV head,
    over several query and KV tiles, against jax.grad of the oracle."""
    shape_q, shape_kv = (2, 128, 8, 16), (2, 128, 2, 16)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(ks[0], shape_q), jax.random.normal(ks[1], shape_kv),
               jax.random.normal(ks[2], shape_kv))
    g = jax.random.normal(ks[3], shape_q)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * g), (0, 1, 2))(q, k, v)

    got = grads(functools.partial(fa.flash_attention_pallas, block_q=32,
                                  block_k=32, interpret=True))
    want = grads(kernel_ref.flash_attention_ref)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def per_block_remat_gives_the_same_gradient():
    params = REF.init_params(jax.random.PRNGKey(8), C)
    bt = batch(C)
    model = build_model(program_config(C))

    def grad(policy):
        return jax.grad(lambda p: model.loss(p, bt, remat_policy=policy)[0])(params)

    for a, b in zip(jax.tree.leaves(grad("full")), jax.tree.leaves(grad("none"))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


def registry_entry_has_the_published_size():
    cfg = get_config("lfm2-8b-a1b")
    sds = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(sds))
    assert total == param_count(cfg)
    assert 8.2e9 < total < 8.4e9  # published 8.3B
    assert 1.45e9 < active_param_count(cfg) < 1.6e9  # published 1.5B active
    assert cfg.block_pattern.count("attn") == 6 and cfg.num_groups == 1


CASES = {f.__name__: f for f in (
    loss_and_grad_match_reference, shares_add_up_to_the_uncut_layer,
    one_expert_batch_matches_reference, conv_is_causal,
    kernel_attention_backward_matches_at_lfm2_grouping,
    per_block_remat_gives_the_same_gradient,
    registry_entry_has_the_published_size)}


@pytest.mark.parametrize("case", list(CASES))
def test_lfm2_moe(case):
    CASES[case]()
