"""Logical-axis sharding layer.

Models annotate activations/params with *logical* axis names; a rule table
maps logical names to mesh axes.  Changing the parallelism strategy (the
hillclimb lever) means swapping the rule table — zero model-code changes.

Baseline strategy (see DESIGN.md §5):
  * activations: batch -> ('pod', 'data'); sequence -> 'model'
    (2-D token sharding: every chip owns a (batch/16 x seq/16) token tile)
  * weights + optimizer state: fully sharded (ZeRO-3/FSDP) over
    ('data', 'model') on the two largest dims, replicated over 'pod'
  * MoE experts: expert dim on 'model' (EP), falls back to FSDP inside
  * KV caches: batch -> 'data', cache sequence -> 'model'
"""
from __future__ import annotations

import contextlib
import re
import threading
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Optional[object]  # mesh axis name, tuple of names, or None


@dataclass(frozen=True)
class AxisRules:
    """logical axis name -> mesh axis (or tuple, or None=replicated)."""

    rules: Dict[str, Axis] = field(default_factory=dict)

    def get(self, name: Optional[str]) -> Axis:
        if name is None:
            return None
        return self.rules.get(name, None)

    def spec(self, *names: Optional[str]) -> P:
        return P(*(self.get(n) for n in names))

    def with_overrides(self, **kw: Axis) -> "AxisRules":
        d = dict(self.rules)
        d.update(kw)
        return AxisRules(d)


# Baseline rule table -------------------------------------------------------
DEFAULT_RULES = AxisRules(
    {
        # activations
        "batch": ("pod", "data"),
        "dp_batch": "data",  # batch sharding that must not touch 'pod'
        "seq": "model",
        "embed_act": None,  # activation feature dim
        "heads_act": None,
        "kv_seq": "model",  # KV-cache sequence dim (decode)
        "kv_long": ("data", "model"),  # long-context cache sequence (batch=1)
        "expert_act": "model",  # dispatched MoE token buffers
        "vocab_act": None,
        # params (FSDP: both biggest dims sharded; ZeRO-3 gathers per layer)
        "embed": "data",
        "vocab": "model",
        "heads": "model",
        "kv_heads": None,
        "head_dim": None,
        "mlp": "model",
        "expert": "model",
        "conv": None,
        "state": None,
        "layers": None,  # stacked scan dim — never sharded
    }
)

_tls = threading.local()


def set_rules(rules: AxisRules) -> None:
    _tls.rules = rules


def get_rules() -> AxisRules:
    return getattr(_tls, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def use_rules(rules: AxisRules):
    prev = get_rules()
    set_rules(rules)
    try:
        yield
    finally:
        set_rules(prev)


def _mesh_axis_names() -> Tuple[str, ...]:
    # 1) explicitly-installed mesh (our own context, survives exotic tracing)
    forced = getattr(_tls, "mesh_axes", None)
    if forced:
        return forced
    # 2) `with mesh:` context (works under jit tracing too)
    from jax.interpreters import pxla

    env_mesh = pxla.thread_resources.env.physical_mesh
    if not env_mesh.empty:
        return tuple(env_mesh.axis_names)
    # 3) abstract mesh (explicit-axis-type mesh contexts)
    am = jax.sharding.get_abstract_mesh()
    return () if am.empty else tuple(am.axis_names)


@contextlib.contextmanager
def force_mesh_axes(names: Tuple[str, ...]):
    """Declare the mesh axes in effect (for code paths where the physical
    mesh context is not visible, e.g. AOT lowering helpers)."""
    prev = getattr(_tls, "mesh_axes", None)
    _tls.mesh_axes = tuple(names)
    try:
        yield
    finally:
        _tls.mesh_axes = prev


def _prune(axis: Axis, present: Tuple[str, ...]) -> Axis:
    """Drop mesh axes that don't exist in the active mesh (e.g. 'pod' on the
    single-pod mesh) so rule tables are mesh-shape agnostic."""
    if axis is None:
        return None
    if isinstance(axis, tuple):
        kept = tuple(a for a in axis if a in present)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return axis if axis in present else None


def logical_spec(*names: Optional[str]) -> P:
    """PartitionSpec for the given logical axis names under current rules,
    pruned to the axes present in the currently-entered mesh. The sentinel
    '*' maps to PartitionSpec.UNCONSTRAINED (partial constraints)."""
    rules = get_rules()
    present = _mesh_axis_names()

    def one(n):
        if n == "*":
            return P.UNCONSTRAINED
        return _prune(rules.get(n), present)

    axes = [one(n) for n in names]
    # a mesh axis may appear at most once: keep the first occurrence
    seen = set()
    out = []
    for a in axes:
        flat = a if isinstance(a, tuple) else (a,) if (a is not None and a is not P.UNCONSTRAINED) else ()
        if any(f in seen for f in flat):
            out.append(None)
            continue
        seen.update(flat)
        out.append(a)
    return P(*out)


def _mesh_axis_sizes() -> Dict[str, int]:
    from jax.interpreters import pxla

    env_mesh = pxla.thread_resources.env.physical_mesh
    if not env_mesh.empty:
        return dict(zip(env_mesh.axis_names, env_mesh.devices.shape))
    return {}


def shd(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names. Shape-aware: axes
    whose mesh size does not divide the dim (e.g. a size-1 decode seq dim)
    are dropped BEFORE duplicate resolution, so later logical names (like
    'mlp_act') can claim the mesh axis. No-op outside a mesh."""
    present = _mesh_axis_names()
    if not present:
        return x
    rules = get_rules()
    sizes = _mesh_axis_sizes()
    axes = []
    for n, dim in zip(names, x.shape):
        if n == "*":
            axes.append(P.UNCONSTRAINED)
            continue
        a = _prune(rules.get(n), present)
        if sizes:
            a = _divisible(a, dim, sizes)
        axes.append(a)
    axes += [None] * (len(x.shape) - len(axes))
    seen = set()
    out = []
    for a in axes:
        flat = a if isinstance(a, tuple) else (a,) if (a is not None and a is not P.UNCONSTRAINED) else ()
        if any(f in seen for f in flat):
            out.append(None)
            continue
        seen.update(flat)
        out.append(a)
    return jax.lax.with_sharding_constraint(x, P(*out))


def batch_axes() -> P:
    return logical_spec("batch")


# ---------------------------------------------------------------------------
# Parameter partition rules (by pytree path)
# ---------------------------------------------------------------------------
# Params are nested dicts.  Rules are (regex over '/'-joined path) ->
# logical axis names per dimension.  First match wins.  Scanned stacks have a
# leading 'layers' dim which is handled automatically (rank mismatch pads
# 'layers' at dim 0).

PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embed/table$", ("vocab", "embed")),
    (r"unembed/table$", ("embed", "vocab")),
    (r"pos_embed/table$", (None, "embed")),
    # attention
    (r"(attn|cross_attn)/wq$", ("embed", "heads", "head_dim")),
    (r"(attn|cross_attn)/wk$", ("embed", "kv_heads", "head_dim")),
    (r"(attn|cross_attn)/wv$", ("embed", "kv_heads", "head_dim")),
    (r"(attn|cross_attn)/wo$", ("heads", "head_dim", "embed")),
    (r"(attn|cross_attn)/bq$", ("heads", "head_dim")),
    (r"(attn|cross_attn)/b[kv]$", ("kv_heads", "head_dim")),
    (r"(attn|cross_attn)/(q_norm|k_norm)$", ("head_dim",)),
    # dense mlp
    (r"mlp/w(i|g)$", ("embed", "mlp")),
    (r"mlp/wo$", ("mlp", "embed")),
    # moe
    (r"moe/router$", ("embed", "expert")),
    (r"moe/w(i|g)$", ("expert", "embed", None)),
    (r"moe/wo$", ("expert", None, "embed")),
    # mamba
    (r"mamba/in_proj$", ("embed", "mlp")),
    (r"mamba/conv_w$", ("conv", "mlp")),
    (r"mamba/conv_b$", ("mlp",)),
    (r"mamba/x_proj$", ("mlp", None)),
    (r"mamba/dt_proj$", (None, "mlp")),
    (r"mamba/dt_bias$", ("mlp",)),
    (r"mamba/A_log$", ("mlp", "state")),
    (r"mamba/D$", ("mlp",)),
    (r"mamba/out_proj$", ("mlp", "embed")),
    # xlstm (mLSTM inner dim d_in uses 'mlp'; heads are few — unsharded)
    (r"mlstm/w_up$", ("embed", "mlp")),
    (r"mlstm/w(q|k|v)$", (None, "embed2", None)),
    (r"mlstm/w(i|f|o)$", ("mlp", None)),
    (r"mlstm/b(i|f|o)$", (None,)),
    (r"mlstm/skip$", ("mlp",)),
    (r"mlstm/w_down$", ("mlp", "embed")),
    (r"slstm/w(i|f|z|o)$", ("embed", "embed2")),
    (r"slstm/r(i|f|z|o)$", ("heads", "head_dim", "head_dim")),
    (r"slstm/b(i|f|z|o)$", ("embed2",)),
    (r"slstm/ffn_w(i|g)$", ("embed", "mlp")),
    (r"slstm/ffn_wo$", ("mlp", "embed")),
    # norms / scalars
    (r"(norm|norm1|norm2|norm3|final_norm|ln)/(scale|bias)$", ("embed",)),
    (r".*", ()),  # default: replicated
)

# 'embed2' logical axis: second d_model-sized dim of square sLSTM weights —
# shard over 'model' to spread the 4x d^2 matrices.
DEFAULT_RULES = DEFAULT_RULES.with_overrides(embed2="model")


def _axis_sizes(mesh: Optional[Mesh]):
    if mesh is not None:
        return dict(zip(mesh.axis_names, mesh.devices.shape))
    return {}


def _divisible(axis: Axis, dim: int, sizes) -> Axis:
    """Drop a sharding axis whose size does not divide the dim — pjit
    argument shardings must be even (e.g. vocab 49155 over 16)."""
    if axis is None or not sizes:
        return axis
    axes = axis if isinstance(axis, tuple) else (axis,)
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    if n and dim % n == 0:
        return axis
    # try the leading sub-tuple
    if isinstance(axis, tuple) and len(axis) > 1:
        return _divisible(axis[:-1], dim, sizes)
    return None


def _spec_for_path(path: str, shape, rules: AxisRules, present, sizes) -> P:
    ndim = len(shape)
    for pattern, names in PARAM_RULES:
        if re.search(pattern, path):
            names_l = list(names)
            if len(names_l) < ndim:  # leading stacked 'layers'/group dims
                names_l = [None] * (ndim - len(names_l)) + names_l
            elif len(names_l) > ndim:
                names_l = names_l[-ndim:] if ndim else []
            axes = [_prune(rules.get(n), present) for n in names_l]
            axes = [_divisible(a, d, sizes) for a, d in zip(axes, shape)]
            # a mesh axis may appear at most once per spec
            seen = set()
            out = []
            for a in axes:
                flat = a if isinstance(a, tuple) else (a,) if a else ()
                if any(f in seen for f in flat):
                    out.append(None)
                    continue
                seen.update(flat)
                out.append(a)
            return P(*out)
    return P(*([None] * ndim))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(params, rules: Optional[AxisRules] = None, mesh: Optional[Mesh] = None):
    """Build a PartitionSpec pytree for a param pytree (leaves may be arrays
    or ShapeDtypeStructs)."""
    rules = rules or get_rules()
    if mesh is not None:
        present = tuple(mesh.axis_names)
    else:
        present = _mesh_axis_names() or ("data", "model")

    sizes = _axis_sizes(mesh)

    def f(path, leaf):
        return _spec_for_path(_path_str(path), tuple(leaf.shape), rules, present, sizes)

    return jax.tree_util.tree_map_with_path(f, params)


def named_shardings(tree_pspecs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
