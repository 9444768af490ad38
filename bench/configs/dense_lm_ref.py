"""Plain reference of a Llama-style dense decoder (RMSNorm, rotary
positions, grouped-query causal softmax attention, gated SiLU MLP, tied
embeddings), its mean cross-entropy loss, its gradient and AdamW, in
straightforward ``jax.numpy``.  The configuration's keys are those of
the model's published ``config.json``.

The weights are made here from the seed, in the layout the program's
train state uses, so that the program and this reference start from the
same parameters and neither takes anything the other made.  The layout
is that of ``repro.models.transformer.init_lm`` for a one-block pattern:
the per-layer weights are stacked on a leading layer axis.

The reference runs at ``Precision.HIGHEST`` in float32; ``dtype`` and
``precision`` let the control run the same arithmetic one precision
lower (bfloat16 activations and weights, float32 optimizer).  The
gradient is accumulated over blocks of rows, each layer recomputed in
the backward pass, so that it fits beside the program's state.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _hd(c) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def init_params(key, c: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded weights: truncated normal (+-2 std) of std
    ``initializer_range``, output projections scaled by 1/sqrt(2 L),
    norm scales 1."""
    L, d, H, KV, hd = (c["num_hidden_layers"], c["hidden_size"],
                       c["num_attention_heads"], c["num_key_value_heads"],
                       _hd(c))
    ff, V = c["intermediate_size"], c["vocab_size"]
    std = c["initializer_range"]
    out_std = std / max(1.0, math.sqrt(2.0 * L))
    ks = jax.random.split(key, 8)

    def tn(k, shape, s):
        return jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * s

    return {
        "embed": {"table": tn(ks[0], (V, d), std)},
        "groups": {"b0": {
            "norm1": {"scale": jnp.ones((L, d), jnp.float32)},
            "attn": {"wq": tn(ks[1], (L, d, H, hd), std),
                     "wk": tn(ks[2], (L, d, KV, hd), std),
                     "wv": tn(ks[3], (L, d, KV, hd), std),
                     "wo": tn(ks[4], (L, H, hd, d), out_std)},
            "norm2": {"scale": jnp.ones((L, d), jnp.float32)},
            "mlp": {"wi": tn(ks[5], (L, d, ff), std),
                    "wg": tn(ks[6], (L, d, ff), std),
                    "wo": tn(ks[7], (L, ff, d), out_std)},
        }},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }


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


def _layer(c, prec, x, p):
    """One decoder layer on x (b, s, d)."""
    ein = functools.partial(jnp.einsum, precision=prec)
    eps = c["rms_norm_eps"]
    h = _rms(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q = _rope(ein("bsd,dhk->bshk", h, a["wq"]), c["rope_theta"])
    k = _rope(ein("bsd,dhk->bshk", h, a["wk"]), c["rope_theta"])
    v = ein("bsd,dhk->bshk", h, a["wv"])
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = ein("bqhk,bthk->bhqt", q, k) / jnp.asarray(math.sqrt(q.shape[-1]), x.dtype)
    n = x.shape[1]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal[None, None], s, jnp.asarray(-jnp.inf, s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    o = ein("bhqt,bthk->bqhk", w, v)
    x = x + ein("bqhk,hkd->bqd", o, a["wo"])
    h = _rms(x, p["norm2"]["scale"], eps)
    m = p["mlp"]
    y = jax.nn.silu(ein("bsd,df->bsf", h, m["wg"])) * ein("bsd,df->bsf", h, m["wi"])
    return x + ein("bsf,fd->bsd", y, m["wo"])


def loss_sum(params, tokens, labels, c, prec=HIGHEST, dtype=jnp.float32):
    """Sum over the block's tokens of the cross-entropy, in ``dtype``."""
    params = jax.tree.map(lambda w: w.astype(dtype), params)
    x = params["embed"]["table"][tokens]

    def body(x, p):
        return _layer(c, prec, x, p), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["groups"]["b0"])
    x = _rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["table"],
                        precision=prec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold).astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _block_fns(cfg_key, prec, dtype):
    c = dict(cfg_key)
    grad = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss_sum(p, t, l, c, prec, dtype)))
    value = jax.jit(lambda p, t, l: loss_sum(p, t, l, c, prec, dtype))
    return grad, value


def _key(c):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


def loss_and_grad(params, batch, c, rows: int, prec=HIGHEST,
                  dtype=jnp.float32) -> Tuple[float, Any]:
    """Mean loss and its gradient over the batch, ``rows`` rows at a
    time."""
    grad_fn, _ = _block_fns(_key(c), prec, dtype)
    toks, labs = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])
    total, gsum = 0.0, None
    for i in range(0, toks.shape[0], rows):
        l, g = grad_fn(params, toks[i:i + rows], labs[i:i + rows])
        total += float(l)
        gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
    n = toks.size
    return total / n, jax.tree.map(lambda g: g.astype(jnp.float32) / n, gsum)


def mean_loss(params, batch, c, rows: int, prec=HIGHEST,
              dtype=jnp.float32) -> float:
    _, fn = _block_fns(_key(c), prec, dtype)
    toks, labs = jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"])
    total = sum(float(fn(params, toks[i:i + rows], labs[i:i + rows]))
                for i in range(0, toks.shape[0], rows))
    return total / toks.size


def lr_scale(step: int, t: Dict[str, Any]) -> float:
    """Linear warmup, then cosine decay to ``min_frac`` (step 1-based)."""
    warm = min(step / max(t["warmup_steps"], 1), 1.0)
    prog = min(max((step - t["warmup_steps"])
                   / max(t["total_steps"] - t["warmup_steps"], 1), 0.0), 1.0)
    return warm * (t["min_frac"] + (1 - t["min_frac"]) * 0.5
                   * (1 + math.cos(math.pi * prog)))


@jax.jit
def _adamw(params, grads, m, v, lr, b1, b2, eps, wd, clip, step):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p),
        params, m, v)
    return params, m, v, g


def train(params, batches: Sequence[Dict[str, Any]], c, rows: int,
          prec=HIGHEST, dtype=jnp.float32):
    """AdamW steps from ``params`` over ``batches``.  Returns the losses,
    the first step's clipped gradient and the final parameters; the
    optimizer runs in float32 whatever ``dtype`` the model runs in."""
    t = c["train"]
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses: List[float] = []
    first = None
    for i, batch in enumerate(batches, start=1):
        loss, g = loss_and_grad(params, batch, c, rows, prec, dtype)
        losses.append(loss)
        params, m, v, gc = _adamw(
            params, g, m, v, jnp.float32(t["lr"] * lr_scale(i, t)),
            t["b1"], t["b2"], t["eps"], t["weight_decay"], t["clip_norm"],
            jnp.float32(i))
        if first is None:
            first = gc
    return losses, first, params
