"""Operations and bytes of an expert-share hybrid decoder (LFM2-MoE:
gated short convolutions, attention, dense and dropless expert
feed-forwards), from its configuration's shapes and the routed rows its
expert layers computed.  The yardstick of ``moe_train_mfu`` and
``expert_gmm_roofline``, kept with the benchmark as ``counts.py`` is.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from harness import program_spans

F32 = 4


def rows_per_step() -> Optional[float]:
    """Routed rows the expert layers computed per step, from the
    program's counters (``moe.rows_held`` over ``moe.steps_read``); None
    where it counted none."""
    reg = program_spans.registry()
    if reg is None or not reg[1].get("moe.steps_read"):
        return None
    return reg[1].get("moe.rows_held", 0) / reg[1]["moe.steps_read"]


def moe_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def expert_fwd_flops_per_row(c: Dict[str, Any]) -> float:
    """One routed (token, expert) row through an expert's three
    products: 2 x 3 d f."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_fwd_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward matrix-product operations per token outside the experts:
    each conv layer's in_proj (d x 3d) and out_proj (d x d); each
    attention layer's projections and causal attention over
    ``(seq+1)/2`` keys on average; the dense MLPs (3 d d_ff); each MoE
    layer's router (d x R); the tied head (d x V)."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    total = 0.0
    for i, kind in enumerate(c["layer_types"][:L]):
        if kind == "conv":
            total += 2 * (3 * d * d + d * d)
        else:
            total += 2 * (2 * d * q + 2 * d * kv) + 2 * 2 * q * (seq + 1) / 2
        if i < c["num_dense_layers"]:
            total += 2 * 3 * d * c["intermediate_size"]
        else:
            total += 2 * d * c["router_experts"]
    return total + 2 * d * c["vocab_size"]


def moe_train_flops(c: Dict[str, Any], seq: int, tokens: float,
                    rows: float) -> float:
    """Forward plus backward operations (3x the forward's matrix
    products) of ``tokens`` tokens whose expert layers computed ``rows``
    routed rows in all.  Recomputation under remat is not counted."""
    return 3.0 * (tokens * dense_fwd_flops_per_token(c, seq)
                  + rows * expert_fwd_flops_per_row(c))


def expert_gmm(c: Dict[str, Any], rows: float, layer_calls: float,
               itemsize: int = F32) -> Tuple[float, float]:
    """(operations, bytes) of the grouped expert products, forward and
    backward (3x the forward), over ``rows`` routed rows in all and
    ``layer_calls`` expert-layer calls: each call reads its held experts'
    three weight matrices once; each row reads its input twice (gate,
    up), writes two hidden rows and reads them back as one product for
    the down projection, and writes its output."""
    d, f, E = c["hidden_size"], c["moe_intermediate_size"], c["num_experts"]
    ops = 3.0 * rows * expert_fwd_flops_per_row(c)
    fwd_bytes = itemsize * (rows * (3 * d + 3 * f) + layer_calls * 3 * E * d * f)
    return ops, 3.0 * fwd_bytes
