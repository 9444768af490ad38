"""Mixture-of-Experts MLP with top-k routing and expert parallelism.

Dense-dispatch formulation (Switch/Mixtral-reference style): tokens are
combined into per-expert buffers with an einsum against the dispatch mask.
The expert dim is sharded over 'model' (EP) — the resharding from the
sequence-sharded residual stream to the expert-sharded buffers lowers to an
all-to-all, which the roofline analysis attributes to the collective term.

``apply_moe_dropless`` is an expert-parallel rank's share instead: it
routes over every expert, keeps the (token, expert) rows of the experts
it holds, all of them, and runs them as grouped matrix products.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import activation, truncated_normal
from repro.parallel.sharding import shd


#: added to the sum of the chosen weights before they are renormalised
#: (the dropless layer, as lfm2_moe's routing does)
TOPK_NORM_EPS = 1e-6


def init_moe(key, d: int, d_ff: int, num_experts: int, num_layers: int, dtype,
             router_experts: int = 0, router_bias: bool = False) -> dict:
    """Weights of ``num_experts`` experts and a router over
    ``router_experts`` (0 -> num_experts), with a selection bias per router
    output where ``router_bias``."""
    kr, ki, kg, ko = jax.random.split(key, 4)
    out_std = 0.02 / max(1.0, (2.0 * num_layers) ** 0.5)
    n_router = router_experts or num_experts
    p = {
        "router": truncated_normal(kr, (d, n_router), 0.02, jnp.float32),
        "wi": truncated_normal(ki, (num_experts, d, d_ff), 0.02, dtype),
        "wg": truncated_normal(kg, (num_experts, d, d_ff), 0.02, dtype),
        "wo": truncated_normal(ko, (num_experts, d_ff, d), out_std, dtype),
    }
    if router_bias:
        p["expert_bias"] = jnp.zeros((n_router,), jnp.float32)
    return p


def router_probs(p: dict, x: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (combine (b,s,E) f32, dispatch (b,s,E) bool, aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (b,s,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)  # (b,s,k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    num_experts = logits.shape[-1]
    dispatch = jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32).sum(axis=-2)  # (b,s,E)
    combine = jnp.einsum("bsk,bske->bse", top_vals, jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32))
    # Switch-style load-balance aux loss.
    frac_tokens = jnp.mean(dispatch, axis=(0, 1)) / top_k  # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))  # (E,)
    aux = num_experts * jnp.sum(frac_tokens * frac_probs)
    return combine, dispatch, aux


def apply_moe(p: dict, x: jax.Array, *, top_k: int, act: str,
              impl: str = None) -> Tuple[jax.Array, jax.Array]:
    """x: (b, s, d) -> (y, aux_loss). impl: 'dense' (reference dispatch) or
    'capacity' (top-C gather per expert — the §Perf hillclimb winner;
    REPRO_MOE_IMPL overrides)."""
    import os

    impl = impl or os.environ.get("REPRO_MOE_IMPL", "dense")
    if impl == "capacity":
        return apply_moe_capacity(p, x, top_k=top_k, act=act)
    combine, dispatch, aux = router_probs(p, x, top_k)
    xin = x  # bf16
    # Dispatch: (E, b, s, d) buffers, expert dim sharded over 'model' (EP).
    expert_in = jnp.einsum("bse,bsd->ebsd", dispatch.astype(xin.dtype), xin)
    expert_in = shd(expert_in, "expert_act", "batch", None, None)
    h = activation(act)(jnp.einsum("ebsd,edf->ebsf", expert_in, p["wg"]))
    h = h * jnp.einsum("ebsd,edf->ebsf", expert_in, p["wi"])
    h = shd(h, "expert_act", "batch", None, None)
    expert_out = jnp.einsum("ebsf,efd->ebsd", h, p["wo"])
    expert_out = shd(expert_out, "expert_act", "batch", None, None)
    y = jnp.einsum("ebsd,bse->bsd", expert_out, combine.astype(xin.dtype))
    y = shd(y, "batch", "seq", None)
    return y, aux.astype(jnp.float32)


def apply_moe_capacity(
    p: dict, x: jax.Array, *, top_k: int, act: str,
    capacity_factor: float = 1.5, block: int = 256, gates=None,
    num_router: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Block-local capacity dispatch (§Perf iteration B2).

    Tokens are grouped into seq-blocks ALIGNED TO THE SEQUENCE SHARDS (block
    = 256 == seq_len/16 at train_4k), and each expert takes its top-C tokens
    *within each block* (C = block·top_k/E·cf). All gathers/scatters index
    inside one block, so no token ever crosses a shard boundary — unlike the
    naive global-top-C (iteration B1, refuted: it all-gathered the entire
    token stream). Buffer volume drops from E× to top_k·cf× of the tokens.
    Overflow tokens are dropped per-expert (Switch-style).  ``gates``
    (b,s,E) f32, the chosen experts' weights and 0 elsewhere, replaces the
    softmax router's (an expert share's, from ``share_gates``; no aux
    loss), and ``num_router`` (0 -> the experts held) is the router's
    width, which sets the capacity."""
    b, s, d = x.shape
    if gates is None:
        combine, dispatch, aux = router_probs(p, x, top_k)  # (b,s,E) f32
        gates = combine * dispatch
    else:
        aux = jnp.zeros((), jnp.float32)
    E = gates.shape[-1]
    bs = min(block, s)
    nb = s // bs
    assert s % bs == 0, (s, bs)
    cap = int(max(1, min(bs, round(bs * top_k / (num_router or E) * capacity_factor))))
    gates = gates.reshape(b, nb, bs, E)
    gT = jnp.swapaxes(gates, 2, 3)  # (b, nb, E, bs)
    topv, topi = jax.lax.top_k(gT, cap)  # (b, nb, E, C) — block-local ids
    keep = (topv > 0.0).astype(x.dtype)
    xb = x.reshape(b, nb, bs, d)
    xb = shd(xb, "batch", "seq", None, None)
    # gather within blocks: (b, nb, E, C, d)
    xin = jnp.take_along_axis(
        xb[:, :, None, :, :], topi[..., None], axis=3
    )
    xin = xin * keep[..., None]
    xin = shd(xin, "batch", "seq", "expert_act", None, None)
    h = activation(act)(jnp.einsum("bnecd,edf->bnecf", xin, p["wg"]))
    h = h * jnp.einsum("bnecd,edf->bnecf", xin, p["wi"])
    out = jnp.einsum("bnecf,efd->bnecd", h, p["wo"])
    out = out * (topv.astype(x.dtype) * keep)[..., None]
    # scatter-add back inside each block
    bi = jnp.arange(b)[:, None, None, None]
    ni = jnp.arange(nb)[None, :, None, None]
    y = jnp.zeros((b, nb, bs, d), x.dtype).at[bi, ni, topi].add(out)
    y = y.reshape(b, s, d)
    y = shd(y, "batch", "seq", None)
    return y, aux.astype(jnp.float32)


def route(p: dict, x: jax.Array, *, top_k: int, score: str, norm_topk: bool,
          scale: float) -> Tuple[jax.Array, jax.Array]:
    """Top-k over every router output: x (T, d) -> (experts (T, k) int32,
    weights (T, k) f32).  ``score`` is 'softmax' or 'sigmoid'; the
    selection bias, where the router has one, only chooses (it takes no
    gradient and weights nothing)."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits, -1)
    choice = probs
    if "expert_bias" in p:
        choice = probs + jax.lax.stop_gradient(p["expert_bias"])
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(probs, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + TOPK_NORM_EPS)
    return idx, w * scale


def share_gates(p: dict, x: jax.Array, *, top_k: int, score: str,
                norm_topk: bool, scale: float, first: int) -> jax.Array:
    """x (b, s, d) -> (b, s, E): each token's weights of the E experts
    held here (``first`` on), 0 where routing did not choose them."""
    b, s, d = x.shape
    idx, w = route(p, x.reshape(b * s, d), top_k=top_k, score=score,
                   norm_topk=norm_topk, scale=scale)
    E = p["wi"].shape[0]
    held = jax.nn.one_hot(idx - first, E, dtype=jnp.float32)  # (T, k, E)
    return jnp.einsum("tk,tke->te", w, held).reshape(b, s, E)


def apply_moe_dropless(p: dict, x: jax.Array, *, top_k: int, act: str,
                       score: str = "softmax", norm_topk: bool = True,
                       scale: float = 1.0, first: int = 0):
    """The share of a MoE layer that experts ``first`` .. ``first + E - 1``
    give (E = the experts whose weights ``p`` holds), dropping nothing.

    x (b, s, d) -> (y, aux, rows): y is the sum over each token's chosen
    held experts of weight x expert(x); aux is 0 (no balance loss); rows
    holds ``held`` (routed rows computed here) and ``max`` (the largest
    held expert's rows).  The row buffer has the static worst case, every
    choice on a held expert: b*s*k rows, sorted by expert, the rows of
    experts held elsewhere last and left out of every group.  Those rows
    are masked before and after each grouped product: on the TPU a
    grouped product leaves rows outside its groups undefined (NaN
    forward, nonzero in its backward), and a mask's gradient passes
    none of it on."""
    b, s, d = x.shape
    n, E = b * s, p["wi"].shape[0]
    x2 = x.reshape(n, d)
    with jax.named_scope("moe.route"):
        idx, w = route(p, x2, top_k=top_k, score=score, norm_topk=norm_topk,
                       scale=scale)
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < E)
        key = jnp.where(held, local, E)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
        tok = order // top_k
    with jax.named_scope("moe.experts"):
        row = held[order][:, None]
        xs = jnp.where(row, jnp.take(x2, tok, axis=0), 0)
        gate = jnp.where(row, jax.lax.ragged_dot(xs, p["wg"], sizes), 0)
        up = jnp.where(row, jax.lax.ragged_dot(xs, p["wi"], sizes), 0)
        out = jax.lax.ragged_dot(activation(act)(gate) * up, p["wo"], sizes)
    with jax.named_scope("moe.combine"):
        wt = jnp.where(held, w.reshape(-1), 0.0)[order]
        out = jnp.where(row, out * wt[:, None].astype(out.dtype), 0)
        unsort = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = jnp.sum(jnp.take(out, unsort, axis=0).reshape(n, top_k, d), axis=1)
    rows = {"held": jnp.sum(sizes), "max": jnp.max(sizes)}
    return y.reshape(b, s, d), jnp.zeros((), jnp.float32), rows
