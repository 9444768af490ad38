"""What the language-model window drivers share: the program's model
and trainer built from a configuration file, weights made on the device
from the seed by the configuration's reference, and the token stream.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

BIG = 10 ** 9  # a step count no run reaches: no save or log falls in it


def program_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, whose keys
    are those of the model's published ``config.json``."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], qkv_bias=c["attention_bias"],
        norm_type="rmsnorm", act=c["hidden_act"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        source=c["source"])


def head_dim(c: Dict[str, Any]) -> int:
    """The head size: ``head_dim`` where the configuration states one,
    else ``hidden_size / num_attention_heads``."""
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


class Tokens:
    """The traffic: ``batch`` rows of ``seq`` tokens per step, uniform
    over the vocabulary, a pure function of (seed, step) so that the
    reference and a resumed job see the same rows."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int):
        self.vocab, self.seq, self.global_batch, self.seed = vocab, seq, batch, seed

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, step])
        toks = rng.integers(0, self.vocab, (self.global_batch, self.seq + 1),
                            dtype=np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def make_weights(ref, c: Dict[str, Any], seed: int):
    """The reference's seeded weights, made on the device in one jitted
    call."""
    import jax

    return jax.jit(lambda k: ref.init_params(k, c))(jax.random.PRNGKey(seed))


def check_layout(model, params) -> None:
    """The weights have exactly the program's parameter layout."""
    import jax

    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the reference's weights do not have the "
                         "program's parameter layout")


def trainer(model, data, root: str, c: Dict[str, Any], mode: str, seed: int):
    """A ``Trainer`` as ``repro.launch.train`` builds it, with the
    configuration's optimizer and schedule, that never saves or logs on
    its own."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig

    t = c["train"]
    step_cfg = TrainStepConfig(
        opt=AdamWConfig(lr=t["lr"], b1=t["b1"], b2=t["b2"], eps=t["eps"],
                        weight_decay=t["weight_decay"],
                        clip_norm=t["clip_norm"]),
        remat_policy=t["remat"], warmup_steps=t["warmup_steps"],
        total_steps=t["total_steps"])
    ckpt = CheckpointManager(root, job=c["name"], mode=mode)
    return Trainer(model, data, ckpt, TrainerConfig(
        total_steps=BIG, save_every=BIG, ckpt_mode=mode, log_every=BIG,
        seed=seed, step_cfg=step_cfg))


def leaf_norms(tree) -> Dict[str, float]:
    """L2 norm of every leaf, the layers of a stacked leaf apart."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        x = jnp.asarray(x, jnp.float32)
        if "groups" in name:
            norms = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))
            for i, n in enumerate(np.asarray(norms)):
                out[f"{name}[{i}]"] = float(n)
        else:
            out[name] = float(jnp.sqrt(jnp.sum(jnp.square(x))))
    return out


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   keep=None) -> float:
    """The largest gap between two sets of leaf norms, each against the
    larger of the reference leaf's norm and the median leaf's."""
    names = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in names]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in names)
