"""AdamW with mixed precision (bf16 compute params / f32 master+moments),
global-norm clipping and weight decay.

Pure pytree functions. ZeRO-1/3 comes for free: optimizer-state leaves
mirror param structure, so `parallel.sharding.param_pspecs` shards master,
m and v exactly like the params (fully sharded over data x model); XLA
inserts the per-layer gathers inside the scan.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def init_opt_state(params) -> Dict[str, Any]:
    f32 = lambda x: jnp.zeros(x.shape, jnp.float32)
    # a buffer of its own even where the params are float32 already, so
    # that a step may be handed (donated) both
    master = jax.tree.map(lambda x: jnp.array(x, jnp.float32, copy=True), params)
    return {
        "step": jnp.zeros((), jnp.int32),
        "master": master,
        "m": jax.tree.map(f32, params),
        "v": jax.tree.map(f32, params),
    }


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def apply_updates(
    params,
    grads,
    state: Dict[str, Any],
    cfg: AdamWConfig,
    lr_scale: jax.Array | float = 1.0,
) -> Tuple[Any, Dict[str, Any], Dict[str, jax.Array]]:
    """One AdamW step. Returns (new_params (compute dtype), new_state, metrics)."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
    lr = cfg.lr * lr_scale
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(g, m, v, mp):
        g = g.astype(jnp.float32) * scale
        m2 = cfg.b1 * m + (1 - cfg.b1) * g
        v2 = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mhat = m2 / b1c
        vhat = v2 / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * mp
        mp2 = mp - lr * delta
        return m2, v2, mp2

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_p = treedef.flatten_up_to(state["master"])
    out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_m = treedef.unflatten([o[0] for o in out])
    new_v = treedef.unflatten([o[1] for o in out])
    new_master = treedef.unflatten([o[2] for o in out])
    new_params = jax.tree.map(
        lambda mp, p: mp.astype(p.dtype), new_master, params
    )
    new_state = {"step": step, "master": new_master, "m": new_m, "v": new_v}
    return new_params, new_state, {"grad_norm": gnorm, "lr": jnp.asarray(lr, jnp.float32)}
