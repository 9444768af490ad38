"""Plain reference of one expert-parallel rank's share of an LFM2-MoE
decoder (``model_type`` ``lfm2_moe``), its mean cross-entropy loss, its
gradient and AdamW, in straightforward ``jax.numpy``.  The configuration's
keys are those of the model's published ``config.json``.

The layers, as the ``lfm2_moe`` model code writes them (``norm`` is
RMSNorm with ``norm_eps``):

- every layer: ``x += mixer(norm(x))``, then ``x += ffn(norm(x))``; the
  last layer is followed by a norm and the head (tied to the embedding);
- ``conv`` mixer: ``B, C, h = split3(in_proj(x))``,
  ``out_proj(C * conv(B * h))``, the convolution depthwise and causal
  over the last ``conv_L_cache`` positions, no biases;
- ``full_attention`` mixer: grouped-query causal softmax attention with
  RMSNorm over each head's query and key (``qk_norm``) before rotary
  positions (the two halves of each head rotated, ``rope_theta``);
- feed-forward: the first ``num_dense_layers`` layers a SwiGLU MLP of
  ``intermediate_size``; every later layer a mixture of experts of
  ``moe_intermediate_size``.  The router scores all ``router_experts``
  experts with a sigmoid, chooses ``num_experts_per_tok`` by score plus
  the selection bias (``use_expert_bias``; the bias weights nothing),
  renormalises the chosen scores to sum 1 (``norm_topk_prob``, with
  ``topk_norm_eps`` added to the sum) and scales them by
  ``routed_scaling_factor``.

Departures from the published model, each shared with the program:

- only experts ``first_expert`` .. ``first_expert + num_experts - 1``
  are held: each layer gives their part of the mixture, and what the
  other ranks' experts would add is left out;
- only ``num_hidden_layers`` of the published layers, the first ones of
  ``layer_types``, and a slice of ``vocab_size`` rows of the vocabulary;
- the selection bias takes no gradient and its load-balancing update is
  left out (weight decay treats it as every other parameter);
- weights are random from the seed, and train in float32.

Each held expert runs on every token of a row block and is weighted by
its gate, which is zero where routing did not choose it: there is no
sort, gather or capacity.  The weights are made here from the seed in the
layout the program's train state uses (``repro.models.transformer``:
one group of ``num_hidden_layers`` blocks ``b0`` ..; every leaf of a
block has a leading group axis of 1).

The reference runs at ``Precision.HIGHEST`` in float32; ``dtype`` and
``prec`` let the control run it one precision lower (bfloat16 model,
float32 optimizer).  The gradient is accumulated over blocks of rows,
each layer recomputed in the backward pass, and AdamW's moments wait on
the host between steps, so that the reference fits on the chip beside
nothing but its own weights and gradient.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
KINDS = {"conv": "conv", "full_attention": "attn"}


def _hd(c) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_kinds(c) -> List[str]:
    return [KINDS[k] for k in c["layer_types"][:c["num_hidden_layers"]]]


def init_params(key, c: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded weights: truncated normal (+-2 std) of std
    ``initializer_range``, output projections scaled by 1/sqrt(2 L), norm
    scales 1, the selection bias 0 (as the ``lfm2_moe`` code starts it)."""
    L, d, H, KV, hd = (c["num_hidden_layers"], c["hidden_size"],
                       c["num_attention_heads"], c["num_key_value_heads"],
                       _hd(c))
    ff, fe, V = (c["intermediate_size"], c["moe_intermediate_size"],
                 c["vocab_size"])
    E, R, K = c["num_experts"], c["router_experts"], c["conv_L_cache"]
    std = c["initializer_range"]
    out_std = std / max(1.0, math.sqrt(2.0 * L))
    keys = iter(jax.random.split(key, 8 * L + 1))

    def tn(shape, s):
        return jax.random.truncated_normal(next(keys), -2.0, 2.0, (1,) + shape,
                                           jnp.float32) * s

    def ones(n):
        return {"scale": jnp.ones((1, n), jnp.float32)}

    blocks = {}
    for i, kind in enumerate(layer_kinds(c)):
        b: Dict[str, Any] = {"norm1": ones(d), "norm2": ones(d)}
        if kind == "conv":
            b["conv"] = {"in_proj": tn((d, 3 * d), std), "conv": tn((K, d), std),
                         "out_proj": tn((d, d), out_std)}
        else:
            b["attn"] = {"wq": tn((d, H, hd), std), "wk": tn((d, KV, hd), std),
                         "wv": tn((d, KV, hd), std), "wo": tn((H, hd, d), out_std),
                         "q_norm": jnp.ones((1, hd), jnp.float32),
                         "k_norm": jnp.ones((1, hd), jnp.float32)}
        if i < c["num_dense_layers"]:
            b["mlp"] = {"wi": tn((d, ff), std), "wg": tn((d, ff), std),
                        "wo": tn((ff, d), out_std)}
        else:
            b["moe"] = {"router": tn((d, R), std),
                        "expert_bias": jnp.zeros((1, R), jnp.float32),
                        "wi": tn((E, d, fe), std), "wg": tn((E, d, fe), std),
                        "wo": tn((E, fe, d), out_std)}
        blocks[f"b{i}"] = b
    return {"embed": {"table": jax.random.truncated_normal(
                next(keys), -2.0, 2.0, (V, d), jnp.float32) * std},
            "groups": blocks,
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _conv(c, ein, h, p):
    bx, cx, xx = jnp.split(ein("bsd,df->bsf", h, p["in_proj"]), 3, axis=-1)
    u, w, K = bx * xx, p["conv"], c["conv_L_cache"]
    n = u.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(up[:, j:j + n] * w[j] for j in range(K))
    return ein("bsd,de->bse", cx * conv, p["out_proj"])


def _attn(c, ein, h, p):
    eps = c["norm_eps"]
    q = _rms(ein("bsd,dhk->bshk", h, p["wq"]), p["q_norm"], eps)
    k = _rms(ein("bsd,dhk->bshk", h, p["wk"]), p["k_norm"], eps)
    v = ein("bsd,dhk->bshk", h, p["wv"])
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = ein("bqhk,bthk->bhqt", q, k) / jnp.asarray(math.sqrt(q.shape[-1]), h.dtype)
    n = h.shape[1]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal[None, None], s, jnp.asarray(-jnp.inf, s.dtype))
    o = ein("bhqt,bthk->bqhk", jax.nn.softmax(s, axis=-1), v)
    return ein("bqhk,hkd->bqd", o, p["wo"])


def _mlp(ein, h, p):
    y = jax.nn.silu(ein("bsd,df->bsf", h, p["wg"])) * ein("bsd,df->bsf", h, p["wi"])
    return ein("bsf,fd->bsd", y, p["wo"])


def chosen(c, ein, h, p):
    """(scores (b,s,R), chosen (b,s,R) bool): the router's sigmoid scores
    and its ``num_experts_per_tok`` choices by score plus bias (an
    expert beats another of equal score if its index is lower)."""
    score = jax.nn.sigmoid(ein("bsd,de->bse", h, p["router"].astype(h.dtype)))
    sel = score + jax.lax.stop_gradient(p["expert_bias"]).astype(h.dtype)
    R = sel.shape[-1]
    lower = jnp.arange(R)[:, None] < jnp.arange(R)[None, :]  # [j, e]: j < e
    beats = (sel[..., :, None] > sel[..., None, :]) | (
        (sel[..., :, None] == sel[..., None, :]) & lower)
    rank = jnp.sum(beats, axis=-2)  # experts that beat e
    return score, rank < c["num_experts_per_tok"]


def _moe(c, ein, h, p):
    score, pick = chosen(c, ein, h, p)
    w = jnp.where(pick, score, 0.0)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + c["topk_norm_eps"])
    w = w * c["routed_scaling_factor"]
    first, E = c["first_expert"], c["num_experts"]
    gate = w[..., first:first + E]  # the held experts' weights
    a = jax.nn.silu(ein("bsd,edf->bsef", h, p["wg"])) * ein("bsd,edf->bsef", h, p["wi"])
    return ein("bsef,efd->bsd", a * gate[..., None], p["wo"])


def _mixed(c, ein, kind, x, p):
    """x after the layer's mixer."""
    h = _rms(x, p["norm1"]["scale"], c["norm_eps"])
    return x + (_conv(c, ein, h, p["conv"]) if kind == "conv"
                else _attn(c, ein, h, p["attn"]))


def _layer(c, prec, i, kind, x, p):
    ein = functools.partial(jnp.einsum, precision=prec)
    x = _mixed(c, ein, kind, x, p)
    h = _rms(x, p["norm2"]["scale"], c["norm_eps"])
    if i < c["num_dense_layers"]:
        return x + _mlp(ein, h, p["mlp"])
    return x + _moe(c, ein, h, p["moe"])


def _blocks(params):
    """The blocks' weights without their group axis."""
    return {k: jax.tree.map(lambda w: w[0], v)
            for k, v in params["groups"].items()}


def loss_sum(params, tokens, labels, c, prec=HIGHEST, dtype=jnp.float32):
    """Sum over the block's tokens of the cross-entropy, in ``dtype``."""
    params = jax.tree.map(lambda w: w.astype(dtype), params)
    blocks = _blocks(params)
    x = params["embed"]["table"][tokens]
    for i, kind in enumerate(layer_kinds(c)):
        fn = jax.checkpoint(functools.partial(_layer, c, prec, i, kind))
        x = fn(x, blocks[f"b{i}"])
    x = _rms(x, params["final_norm"]["scale"], c["norm_eps"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["table"],
                        precision=prec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold).astype(jnp.float32))


def choices(params, tokens, c, prec=HIGHEST):
    """The router's choices (b, s, MoE layers, R) bool over a block of
    rows, in float32."""
    ein = functools.partial(jnp.einsum, precision=prec)
    blocks = _blocks(params)
    x = params["embed"]["table"][tokens]
    out = []
    for i, kind in enumerate(layer_kinds(c)):
        p = blocks[f"b{i}"]
        if i >= c["num_dense_layers"]:
            h = _rms(_mixed(c, ein, kind, x, p), p["norm2"]["scale"], c["norm_eps"])
            out.append(chosen(c, ein, h, p["moe"])[1])
        x = _layer(c, prec, i, kind, x, p)
    return jnp.stack(out, axis=2)


@functools.lru_cache(maxsize=None)
def _block_fns(cfg_key, prec, dtype):
    c = dict(cfg_key)
    grad = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss_sum(p, t, l, c, prec, dtype)))
    pick = jax.jit(lambda p, t: choices(p, t, c, prec))
    return grad, pick


def _key(c):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()
                        if isinstance(v, (int, float, str, bool, list))))


_acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)


def loss_and_grad(params, batch, c, rows: int, prec=HIGHEST,
                  dtype=jnp.float32) -> Tuple[float, Any]:
    """Mean loss and its gradient over the batch, ``rows`` rows at a
    time; ``params`` on the device."""
    grad_fn, _ = _block_fns(_key(c), prec, dtype)
    toks, labs = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])
    total, gsum = 0.0, None
    for i in range(0, toks.shape[0], rows):
        l, g = grad_fn(params, toks[i:i + rows], labs[i:i + rows])
        total += float(l)
        gsum = g if gsum is None else _acc(gsum, g)
        del g
    n = toks.size
    return total / n, jax.tree.map(lambda g: g.astype(jnp.float32) / n, gsum)


def batch_choices(params, batch, c, rows: int) -> np.ndarray:
    """``choices`` over the whole batch, ``rows`` rows at a time."""
    _, pick = _block_fns(_key(c), HIGHEST, jnp.float32)
    toks = jnp.asarray(batch["tokens"])
    return np.concatenate([np.asarray(pick(params, toks[i:i + rows]))
                           for i in range(0, toks.shape[0], rows)])


def lr_scale(step: int, t: Dict[str, Any]) -> float:
    """Linear warmup, then cosine decay to ``min_frac`` (step 1-based)."""
    warm = min(step / max(t["warmup_steps"], 1), 1.0)
    prog = min(max((step - t["warmup_steps"])
                   / max(t["total_steps"] - t["warmup_steps"], 1), 0.0), 1.0)
    return warm * (t["min_frac"] + (1 - t["min_frac"]) * 0.5
                   * (1 + math.cos(math.pi * prog)))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adamw_leaf(p, g, m, v, lr, b1, b2, eps, wd, scale, step):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p), m, v, g


@jax.jit
def _sq_norm(g):
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))


def train(params, batches: Sequence[Dict[str, Any]], c, rows: int,
          prec=HIGHEST, dtype=jnp.float32):
    """AdamW steps from ``params`` over ``batches``.  Returns the losses,
    the first step's clipped gradient (on the host) and the final
    parameters; the optimizer runs in float32 whatever ``dtype`` the
    model runs in, one leaf at a time, its moments on the host."""
    t = c["train"]
    p = jax.tree.map(jnp.array, params)  # a copy: the steps donate it
    m = jax.tree.map(lambda w: np.zeros(w.shape, np.float32), params)
    v = jax.tree.map(lambda w: np.zeros(w.shape, np.float32), params)
    losses: List[float] = []
    first = None
    for i, batch in enumerate(batches, start=1):
        loss, g = loss_and_grad(p, batch, c, rows, prec, dtype)
        losses.append(loss)
        gnorm = float(jnp.sqrt(_sq_norm(g)))
        scale = min(1.0, t["clip_norm"] / max(gnorm, 1e-12))
        lr = t["lr"] * lr_scale(i, t)
        flat_p, tree = jax.tree.flatten(p)
        out_p, out_m, out_v, out_g = [], [], [], []
        for pl, gl, ml, vl in zip(flat_p, jax.tree.leaves(g),
                                  jax.tree.leaves(m), jax.tree.leaves(v)):
            np_, nm, nv, ng = _adamw_leaf(
                pl, gl, jnp.asarray(ml), jnp.asarray(vl), jnp.float32(lr),
                t["b1"], t["b2"], t["eps"], t["weight_decay"],
                jnp.float32(scale), jnp.float32(i))
            out_p.append(np_)
            out_m.append(np.asarray(nm))
            out_v.append(np.asarray(nv))
            if first is None:
                out_g.append(np.asarray(ng))
        del g, flat_p
        p = jax.tree.unflatten(tree, out_p)
        m, v = jax.tree.unflatten(tree, out_m), jax.tree.unflatten(tree, out_v)
        if first is None:
            first = jax.tree.unflatten(tree, out_g)
    return losses, first, p
