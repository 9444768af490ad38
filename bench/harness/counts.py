"""Operations and bytes that a computation needs, from its shapes.

These are the yardstick of the roofline and utilisation metrics, kept
with the benchmark so that no change to the program changes them.  A
roofline share is the least time the chip could take (the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the
measured time.
"""
from __future__ import annotations

from typing import Dict, Tuple

F32 = 4

#: Elementwise operations per (job, site) pair of the decide kernel,
#: counted from Algorithm 1 as the kernel evaluates it: t_cost (2 adds),
#: energy gate (mul, cmp), class-C gate (cmp), time gate (mul, cmp), the
#: two ands of ``ok``; avoided grid-seconds (2 min, sub, max); benefit
#: (sub, mul, sub, add); validity (2 cmp, max, 2 and); the lexicographic
#: argbest (select, max, cmp, and, select, min, cmp, and, select, min).
DECIDE_OPS_PER_PAIR = 31


def decide_kernel(jobs: int, sites: int) -> Tuple[float, float]:
    """(operations, bytes) of one decide call over ``jobs`` x ``sites``
    real rows: the float32 transfer-time grid, five per-job columns
    (load time, remaining, current window, source load, source id),
    three per-site columns (window, queue load, slot penalty), and one
    destination per job.  Padding lanes are not counted, so they show as
    waste."""
    ops = DECIDE_OPS_PER_PAIR * jobs * sites
    nbytes = F32 * (jobs * sites + 5 * jobs + 3 * sites + jobs)
    return float(ops), float(nbytes)


def causal_attention_fwd(batch: int, heads: int, seq: int,
                         head_dim: int, itemsize: int = F32
                         ) -> Tuple[float, float]:
    """(operations, bytes) of one causal attention forward: the two
    matrix products (scores and weighted values) over the
    ``seq*(seq+1)/2`` query-key pairs a causal mask keeps, and q, k, v
    and the output read or written once."""
    pairs = seq * (seq + 1) / 2
    ops = 2 * 2 * batch * heads * head_dim * pairs
    nbytes = 4 * batch * seq * heads * head_dim * itemsize
    return float(ops), float(nbytes)


def lm_train_flops_per_token(cfg: Dict[str, int], seq: int) -> float:
    """Forward plus backward operations per token of a dense decoder
    with a SwiGLU MLP and a (tied or untied) output head: 3x the
    forward's matrix products, the forward being 2 x (attention
    projections 4 d^2 + MLP 3 d d_ff) per layer, the head 2 d V, and
    causal attention 4 d ((seq+1)/2) per layer.  Recomputation under
    rematerialisation is not counted."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    proj = 2 * d * q + 2 * d * kv  # wq + wo, wk + wv
    mlp = 3 * d * cfg["intermediate_size"]
    attn = 2 * 2 * q * (seq + 1) / 2
    fwd = L * (2 * (proj + mlp) + attn) + 2 * d * cfg["vocab_size"]
    return float(3 * fwd)


def roofline_seconds(ops: float, nbytes: float,
                     peak: Dict[str, float]) -> Tuple[float, str]:
    """The least time on the chip and which bound sets it."""
    t_ops = ops / peak["flops_bf16"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
