"""Fused, batched policy decide kernels (the PR 7 compiled decide path).

The per-tick hot loop of every migration policy is the fused
feasibility + benefit + lexicographic-argbest pass of
:func:`repro.core.orchestrator.score_migrations` — a ``(jobs × sites)``
grid evaluated once per simulator tick.  At fleet scale
(O(100) sites × O(100k) jobs) and at sweep scale (thousands of
concurrent Monte-Carlo cells) that pass is numpy-*dispatch*-bound: ~40
small elementwise kernels per cell per tick.  This module collapses it
three ways:

* **batching** — many cells' candidate rows are stacked into one padded
  ``(cells × jobs × sites)`` tensor and scored in a single pass
  (:func:`score_rows`), so dispatch cost amortizes over the whole batch;
* **bucketed padding** — job counts are padded to the next power of two
  (min 8) and site counts to a multiple of 8, so job-count drift between
  ticks reuses a handful of shapes instead of recompiling/reallocating
  per tick (``pad_jobs`` / ``pad_sites``);
* **compilation** — the same fused math is available as one
  ``jax.jit``-compiled XLA program and as a pallas kernel following the
  repo's ``kernels/flash_attention.py`` idiom (jobs on the 128-wide lane
  axis, sites on sublanes, VMEM-tiled over both, masked padding lanes,
  running lexicographic argbest across site tiles).

Backend selection (:func:`backend` / :func:`set_backend`):

* ``numpy`` — the default everywhere except TPU.  Batched numpy mirrors
  ``score_migrations`` op for op with a leading batch axis, so action
  lists are **bit-identical** to the per-cell grids and to the
  ``decide_scalar`` oracles; every gated benchmark digit is produced by
  this backend.
* ``jit`` — the fused kernel as one jitted XLA call in float64
  (``jax.enable_x64``): same math, one dispatch.
* ``pallas`` — the tiled kernel (float32 compares on a float64
  ``t_transfer``, ``interpret=True`` off-TPU); auto-selected on TPU.

The ``REPRO_DECIDE_BACKEND`` environment variable overrides the default.
Compiled backends return only the argbest destination per row; the rare
reserved-aware fallback path recomputes the numpy feasibility grids
lazily (see ``FeasibilityAwarePolicy._commit``).

Padding-lane invariants (why masked lanes can never win):  padded site
columns carry ``bw == 0`` and ``window == 0`` so ``t_transfer = inf``
fails every feasibility gate; padded job rows carry ``bw == 0`` across
all sites (and ``ckpt == 1.0``, never 0, so no ``0/0`` NaN) and resolve
to destination ``-1``.  All reductions use exact neutral elements
(``-inf`` for max, ``+inf`` for min), and ``argmax`` keeps numpy's
first-occurrence rule, preserving the scalar tie-break key
``(-benefit, t_transfer, sid)``.
"""
from __future__ import annotations

import functools
import operator
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import telemetry
from repro.core import feasibility as fz

# ---------------------------------------------------------------------------
# Shared scalar helpers
# ---------------------------------------------------------------------------

_PPF_CACHE: Dict[float, float] = {}


def _norm_ppf_cached(eps: float) -> float:
    """Standard-normal inverse CDF, memoized (the stochastic gate's
    eps-quantile; moved here from orchestrator so kernels never import
    the policy module)."""
    got = _PPF_CACHE.get(eps)
    if got is None:
        import statistics

        got = _PPF_CACHE[eps] = statistics.NormalDist().inv_cdf(eps)
    return got


# ---------------------------------------------------------------------------
# Backend selection
# ---------------------------------------------------------------------------

_BACKENDS = ("numpy", "jit", "pallas")
_backend: Optional[str] = None


def backend() -> str:
    """The active decide backend: ``REPRO_DECIDE_BACKEND`` env override,
    else ``pallas`` on TPU, else ``numpy``."""
    global _backend
    if _backend is None:
        env = os.environ.get("REPRO_DECIDE_BACKEND", "").strip().lower()
        if env:
            if env not in _BACKENDS:
                raise ValueError(
                    f"REPRO_DECIDE_BACKEND must be one of {_BACKENDS}, "
                    f"not {env!r}")
            _backend = env
        else:
            import jax

            _backend = "pallas" if jax.default_backend() == "tpu" else "numpy"
    return _backend


def set_backend(name: Optional[str]) -> None:
    """Force a backend (tests/benchmarks); ``None`` re-derives the
    default on next use."""
    global _backend
    if name is not None and name not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, not {name!r}")
    _backend = name


# ---------------------------------------------------------------------------
# Row extraction + padded batching
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ScoreParams:
    """The scalar knobs of the fused kernel (one immutable bundle so a
    batch group can assert every cell shares them)."""

    alpha: float
    gamma: float
    beta: float
    queue_penalty_s: float
    min_benefit_s: float
    eps: float = 0.0
    forecast_sigma_s: float = 0.0

    @property
    def use_stoch(self) -> bool:
        return self.eps > 0.0 and self.forecast_sigma_s > 0.0

    @property
    def ppf_sigma(self) -> float:
        return (_norm_ppf_cached(self.eps) * self.forecast_sigma_s
                if self.use_stoch else 0.0)


@dataclass
class StateRows:
    """One cell's candidate rows, gathered from the SoA columns — the
    exact inputs :func:`score_migrations` reads, params-free so one
    extraction serves every backend.  ``k`` jobs × ``n`` sites."""

    sizes: np.ndarray      # (k,)  ckpt_bytes
    t_loads: np.ndarray    # (k,)
    rem: np.ndarray        # (k,)  remaining_s
    cur_green: np.ndarray  # (k,)  renewable window at the source, else 0
    load_src: np.ndarray   # (k,)  site_load at the source
    s_i: np.ndarray        # (k,)  source sid
    bw: np.ndarray         # (k, n) bandwidth_bps rows
    W: np.ndarray          # (n,)  site_window_s
    bq_load: np.ndarray    # (n,)
    free_slots: np.ndarray  # (n,)
    # (n,) battery state-of-charge kWh when the cell reports storage,
    # else None.  Carried for battery-aware compiled scoring; the
    # numpy scorer ignores it, so scores stay bit-identical either way.
    soc: Optional[np.ndarray] = None

    @property
    def k(self) -> int:
        return len(self.sizes)

    @property
    def n(self) -> int:
        return len(self.W)


def rows_from_state(state, cand: np.ndarray,
                    bw_grid: Optional[np.ndarray] = None) -> StateRows:
    """Gather one cell's :class:`StateRows` from a ``ClusterState`` and
    its candidate index array."""
    soa = state.soa
    W = state.site_window_s
    s_i = soa.site[cand]
    if bw_grid is None:
        bw_grid = state.bandwidth_bps[s_i, :]
    return StateRows(
        sizes=soa.ckpt_bytes[cand], t_loads=soa.t_load_s[cand],
        rem=soa.remaining_s[cand],
        cur_green=np.where(state.site_renewable[s_i], W[s_i], 0.0),
        load_src=state.site_load[s_i], s_i=s_i, bw=bw_grid, W=W,
        bq_load=state.site_bq_load, free_slots=state.site_free_slots,
        soc=(state.site_battery_soc if state.battery is not None else None))


def pad_jobs(k: int) -> int:
    """Job-axis padding bucket: next power of two, floor 8."""
    p = 8
    while p < k:
        p <<= 1
    return p


def pad_sites(n: int) -> int:
    """Site-axis padding bucket: next multiple of 8 (the pallas wrapper
    re-pads to a whole number of site tiles internally)."""
    return ((n + 7) // 8) * 8


@dataclass
class ScoreBatch:
    """Padded, stacked rows for ``B`` cells: ``(B, K)`` job columns,
    ``(B, S)`` site columns, ``(B, K, S)`` bandwidth.  Padding values are
    chosen so masked lanes are infeasible (see module docstring)."""

    sizes: np.ndarray      # (B, K) pad 1.0
    t_loads: np.ndarray    # (B, K) pad 0.0
    rem: np.ndarray        # (B, K) pad 0.0
    cur_green: np.ndarray  # (B, K) pad 0.0
    load_src: np.ndarray   # (B, K) pad 0.0
    s_i: np.ndarray        # (B, K) int32, pad 0
    bw: np.ndarray         # (B, K, S) pad 0.0
    W: np.ndarray          # (B, S) pad 0.0
    bq_load: np.ndarray    # (B, S) pad 0.0
    free_slots: np.ndarray  # (B, S) pad 1
    n_jobs: Tuple[int, ...]
    n_sites: Tuple[int, ...]
    # (B, S) battery SoC kWh, pad 0.0 — None unless some cell reports
    # storage (reserved for battery-aware compiled scoring; unused by
    # the numpy scorer so batch scores never depend on it)
    soc: Optional[np.ndarray] = None


def _ragged_idx(lens: np.ndarray, stride: int) -> np.ndarray:
    """Flat scatter positions for ragged rows: row ``b``'s ``lens[b]``
    elements land at ``b*stride + [0..lens[b])``."""
    total = int(lens.sum())
    within = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
    return np.repeat(np.arange(len(lens)) * stride, lens) + within


def build_batch(rows: Sequence[StateRows]) -> ScoreBatch:
    """Stack cells into one bucket-padded :class:`ScoreBatch`.

    Ragged rows are placed with one concatenate + one flat scatter per
    column (constant dispatch count per batch) rather than B slice
    assignments per column — at sweep scale (B ~ 1000 tiny cells) the
    python stacking loop would otherwise dominate the fused kernel.
    """
    B = len(rows)
    ks = np.fromiter((r.k for r in rows), np.int64, B)
    ns = np.fromiter((r.n for r in rows), np.int64, B)
    K = pad_jobs(int(ks.max()))
    S = pad_sites(int(ns.max()))
    jidx = _ragged_idx(ks, K)
    sidx = _ragged_idx(ns, S)

    def jcol(vals, fill, dtype=np.float64):
        out = np.full(B * K, fill, dtype=dtype)
        out[jidx] = np.concatenate(vals)
        return out.reshape(B, K)

    def scol(vals, fill, dtype=np.float64):
        out = np.full(B * S, fill, dtype=dtype)
        out[sidx] = np.concatenate(vals)
        return out.reshape(B, S)

    # bw is ragged in both axes: element (b, j, s) lives at flat
    # (b*K + j)*S + s — jidx already enumerates (b*K + j) per real job
    widths = np.repeat(ns, ks)  # sites per (cell, job) row
    bw = np.zeros(B * K * S)
    bw[np.repeat(jidx * S, widths)
       + _ragged_idx(widths, 0)] = np.concatenate(
           [r.bw.ravel() for r in rows])
    return ScoreBatch(
        sizes=jcol([r.sizes for r in rows], 1.0),
        t_loads=jcol([r.t_loads for r in rows], 0.0),
        rem=jcol([r.rem for r in rows], 0.0),
        cur_green=jcol([r.cur_green for r in rows], 0.0),
        load_src=jcol([r.load_src for r in rows], 0.0),
        s_i=jcol([r.s_i for r in rows], 0, np.int32),
        bw=bw.reshape(B, K, S),
        W=scol([r.W for r in rows], 0.0),
        bq_load=scol([r.bq_load for r in rows], 0.0),
        free_slots=scol([r.free_slots for r in rows], 1, np.int64),
        n_jobs=tuple(int(k) for k in ks),
        n_sites=tuple(int(n) for n in ns),
        soc=(scol([(r.soc if r.soc is not None else np.zeros(r.n))
                   for r in rows], 0.0)
             if any(r.soc is not None for r in rows) else None))


def batch_from_states(states: Sequence, cands: Sequence[np.ndarray],
                      bw_grids: Optional[Sequence[np.ndarray]] = None,
                      ) -> ScoreBatch:
    """Build a :class:`ScoreBatch` straight from many ``ClusterState``
    snapshots with CROSS-CELL vectorized gathers: one concatenate + one
    fancy-index per column over all cells at once, instead of ~9 tiny
    numpy dispatches per cell (:func:`rows_from_state`) — at sweep scale
    the per-cell dispatch cost would dominate the fused kernel itself.
    Values are gathered with the exact same index arithmetic, so the
    resulting batch is element-identical to the per-cell path.

    ``bw_grids`` optionally carries per-cell pre-hardened bandwidth rows
    (plan-ahead's forecast-outage hardening); otherwise rows are gathered
    from each state's advertised ``bandwidth_bps`` matrix.
    """
    B = len(states)
    ks = np.fromiter((len(c) for c in cands), np.int64, B)
    ns = np.fromiter((s.n_sites for s in states), np.int64, B)
    K = pad_jobs(int(ks.max()))
    S = pad_sites(int(ns.max()))
    job_lens = np.fromiter((len(s.soa.jids) for s in states), np.int64, B)
    job_offs = np.cumsum(job_lens) - job_lens
    site_offs = np.cumsum(ns) - ns
    cand_g = np.concatenate(cands) + np.repeat(job_offs, ks)
    sizes = np.concatenate([s.soa.ckpt_bytes for s in states])[cand_g]
    t_loads = np.concatenate([s.soa.t_load_s for s in states])[cand_g]
    rem = np.concatenate([s.soa.remaining_s for s in states])[cand_g]
    s_i = np.concatenate([s.soa.site for s in states])[cand_g]
    W_cat = np.concatenate([s.site_window_s for s in states])
    s_g = s_i + np.repeat(site_offs, ks)
    cur_green = np.where(
        np.concatenate([s.site_renewable for s in states])[s_g],
        W_cat[s_g], 0.0)
    load_src = np.concatenate([s.site_load for s in states])[s_g]

    widths = np.repeat(ns, ks)  # destination count per (cell, job) row
    if bw_grids is not None:
        bw_vals = np.concatenate([g.ravel() for g in bw_grids])
    else:
        # gather each job's bandwidth row out of the cells' flattened
        # (n, n) matrices: row base = cell offset + s_i * n
        mat_lens = ns * ns
        row_base = (np.repeat(np.cumsum(mat_lens) - mat_lens, ks)
                    + s_i * widths)
        bw_vals = np.concatenate(
            [np.asarray(s.bandwidth_bps).ravel() for s in states])[
                np.repeat(row_base, widths) + _ragged_idx(widths, 0)]

    jidx = _ragged_idx(ks, K)
    sidx = _ragged_idx(ns, S)

    def jcol(vals, fill, dtype=np.float64):
        out = np.full(B * K, fill, dtype=dtype)
        out[jidx] = vals
        return out.reshape(B, K)

    def scol(vals, fill, dtype=np.float64):
        out = np.full(B * S, fill, dtype=dtype)
        out[sidx] = np.concatenate(vals)
        return out.reshape(B, S)

    bw = np.zeros(B * K * S)
    bw[np.repeat(jidx * S, widths) + _ragged_idx(widths, 0)] = bw_vals
    return ScoreBatch(
        sizes=jcol(sizes, 1.0), t_loads=jcol(t_loads, 0.0),
        rem=jcol(rem, 0.0), cur_green=jcol(cur_green, 0.0),
        load_src=jcol(load_src, 0.0), s_i=jcol(s_i, 0, np.int32),
        bw=bw.reshape(B, K, S),
        W=scol([s.site_window_s for s in states], 0.0),
        bq_load=scol([s.site_bq_load for s in states], 0.0),
        free_slots=scol([s.site_free_slots for s in states], 1, np.int64),
        n_jobs=tuple(int(k) for k in ks),
        n_sites=tuple(int(n) for n in ns),
        soc=(scol([s.site_battery_soc for s in states], 0.0)
             if any(s.battery is not None for s in states) else None))


def score_states(states: Sequence, cands: Sequence[np.ndarray],
                 params: ScoreParams,
                 bw_grids: Optional[Sequence[np.ndarray]] = None,
                 backend_name: Optional[str] = None) -> List[np.ndarray]:
    """Batch + score many cells' candidate rows in one fused pass;
    returns one un-padded ``(k_i,)`` destination array per cell — or
    ``None`` for a cell where no row found a destination, so callers
    skip their commit path without even a per-cell ``any()`` (the
    no-migration tick is the overwhelmingly common case at sweep
    scale, and the check is one batched reduction here)."""
    if not states:
        return []
    with telemetry.span("decide.batch"):
        batch = batch_from_states(states, cands, bw_grids)
    dest = score_batch(batch, params, backend_name)
    live = (dest >= 0).any(axis=1)
    return [dest[b, :len(c)] if live[b] else None
            for b, c in enumerate(cands)]


# ---------------------------------------------------------------------------
# numpy backend — the parity oracle for the compiled variants
# ---------------------------------------------------------------------------


def _score_numpy(batch: ScoreBatch, params: ScoreParams) -> np.ndarray:
    """The fused kernel with a leading batch axis, op-for-op identical to
    per-cell :func:`score_migrations` (every operation is elementwise or
    a per-lane reduction with exact neutral elements, so real lanes are
    bit-identical to the unbatched pass).  Returns ``(B, K)`` argbest
    destinations, ``-1`` where no destination is valid."""
    with np.errstate(divide="ignore"):
        tt = 8.0 * batch.sizes[:, :, None] / batch.bw
    W = batch.W[:, None, :]
    t_cost = tt + batch.t_loads[:, :, None] + fz.T_DOWNTIME_S
    energy_ok = (fz.P_SYS_KW / fz.P_NODE_KW) * tt < W
    not_c = tt < fz.CLASS_B_MAX_S
    if params.use_stoch:
        window_lo = W + params.ppf_sigma
        time_ok = t_cost < params.alpha * np.maximum(window_lo, 0.0)
    else:
        time_ok = t_cost < params.alpha * W
    ok = time_ok & energy_ok & not_c
    rem = batch.rem[:, :, None]
    avoided = np.maximum(
        0.0, np.minimum(W, rem) - np.minimum(batch.cur_green[:, :, None], rem))
    benefit = (params.gamma * avoided
               - (params.beta * params.queue_penalty_s)
               * (batch.bq_load[:, None, :] - batch.load_src[:, :, None]))
    benefit = benefit + np.where(batch.free_slots <= 0,
                                 -params.queue_penalty_s, 0.0)[:, None, :]
    sid = np.arange(batch.W.shape[1])
    valid = (ok
             & (sid[None, None, :] != batch.s_i[:, :, None])
             & (benefit > np.maximum(t_cost, params.min_benefit_s)))
    b = np.where(valid, benefit, -np.inf)
    mb = b.max(axis=2)
    tie = valid & (b == mb[..., None])
    ttm = np.where(tie, tt, np.inf)
    tie = tie & (ttm == ttm.min(axis=2)[..., None])
    return np.where(np.isfinite(mb), tie.argmax(axis=2), -1)


# ---------------------------------------------------------------------------
# jit backend — the same math as one compiled XLA program (float64)
# ---------------------------------------------------------------------------

_JIT_FN = None


def _jit_fn():
    global _JIT_FN
    if _JIT_FN is None:
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("use_stoch",))
        def fn(sizes, t_loads, rem, cur_green, load_src, s_i, bw, W,
               bq_load, free_slots, alpha, gamma, betaqp, queue_penalty_s,
               min_benefit_s, ppf_sigma, use_stoch):
            tt = 8.0 * sizes[:, :, None] / bw
            Wn = W[:, None, :]
            t_cost = tt + t_loads[:, :, None] + fz.T_DOWNTIME_S
            energy_ok = (fz.P_SYS_KW / fz.P_NODE_KW) * tt < Wn
            not_c = tt < fz.CLASS_B_MAX_S
            if use_stoch:
                time_ok = t_cost < alpha * jnp.maximum(Wn + ppf_sigma, 0.0)
            else:
                time_ok = t_cost < alpha * Wn
            ok = time_ok & energy_ok & not_c
            remn = rem[:, :, None]
            avoided = jnp.maximum(
                0.0, jnp.minimum(Wn, remn)
                - jnp.minimum(cur_green[:, :, None], remn))
            benefit = (gamma * avoided
                       - betaqp * (bq_load[:, None, :]
                                   - load_src[:, :, None]))
            benefit = benefit + jnp.where(
                free_slots <= 0, -queue_penalty_s, 0.0)[:, None, :]
            sid = jax.lax.broadcasted_iota(jnp.int32, tt.shape, 2)
            valid = (ok
                     & (sid != s_i[:, :, None])
                     & (benefit > jnp.maximum(t_cost, min_benefit_s)))
            b = jnp.where(valid, benefit, -jnp.inf)
            mb = b.max(axis=2)
            tie = valid & (b == mb[..., None])
            ttm = jnp.where(tie, tt, jnp.inf)
            tie = tie & (ttm == ttm.min(axis=2)[..., None])
            return jnp.where(jnp.isfinite(mb), tie.argmax(axis=2), -1)

        _JIT_FN = fn
    return _JIT_FN


def _count_lanes(batch: ScoreBatch, lanes: int) -> None:
    """Counters ``decide.rows`` (the batch's unpadded job x site pairs)
    and ``decide.lanes`` (the pairs a backend computes, padding
    included), summed only while telemetry records."""
    if telemetry.active():
        telemetry.count("decide.rows", sum(map(operator.mul, batch.n_jobs,
                                               batch.n_sites)))
        telemetry.count("decide.lanes", lanes)


def _score_jit(batch: ScoreBatch, params: ScoreParams) -> np.ndarray:
    """One fused XLA dispatch in float64 (scalar knobs are traced, so
    value changes never recompile; only padding-bucket shape changes
    do)."""
    import jax

    _count_lanes(batch, batch.bw.size)
    with jax.enable_x64(True):
        with telemetry.span("decide.launch"):
            out = _jit_fn()(
                batch.sizes, batch.t_loads, batch.rem, batch.cur_green,
                batch.load_src, batch.s_i, batch.bw, batch.W, batch.bq_load,
                batch.free_slots, params.alpha, params.gamma,
                params.beta * params.queue_penalty_s, params.queue_penalty_s,
                params.min_benefit_s, params.ppf_sigma,
                use_stoch=params.use_stoch)
        with telemetry.span("decide.fetch"):
            return np.asarray(out)


# ---------------------------------------------------------------------------
# pallas backend — jobs on lanes, sites on sublanes, tiled over both
# ---------------------------------------------------------------------------

NEG_INF = -2.0e38  # large-but-finite f32 sentinels (flash_attention idiom)
POS_INF = 2.0e38
BIG_IDX = 2 ** 30

_BLOCK_J = 512  # job lanes per tile (a multiple of 128, or all of K)
_BLOCK_S = 128  # site sublanes per tile (a multiple of 8, or all of S)


def _dest_kernel(tt_ref, t_loads_ref, rem_ref, cur_green_ref, load_src_ref,
                 s_i_ref, W_ref, bq_load_ref, free_pen_ref, dest_ref,
                 mb_scr, mtt_scr, mdest_scr, *, alpha, gamma, betaqp,
                 min_benefit_s, ppf_sigma, use_stoch, block_s, n_s_blocks):
    """One (batch, job-tile, site-tile) grid step over a ``(bs, bj)``
    tile — sites on sublanes, jobs on lanes: score the tile, fold it into
    the running lexicographic argbest held in VMEM scratch, and emit
    destinations after the last site tile.

    Job columns arrive as ``(1, bj)`` rows and site columns as ``(bs, 1)``
    columns, so every operand broadcasts to the tile without a relayout.
    The cross-tile update keeps the *earlier* tile on exact
    (benefit, t_transfer) ties, and the within-tile reduction takes the
    lowest sid among tied sublanes — together reproducing numpy argmax's
    first-occurrence (lowest-sid) rule globally.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        mb_scr[...] = jnp.full(mb_scr.shape, NEG_INF, jnp.float32)
        mtt_scr[...] = jnp.full(mtt_scr.shape, POS_INF, jnp.float32)
        mdest_scr[...] = jnp.full(mdest_scr.shape, -1, jnp.int32)

    tt = tt_ref[...]                 # (bs, bj); +inf on dead/padded lanes
    W = W_ref[...]                   # (bs, 1)
    t_cost = tt + t_loads_ref[...] + fz.T_DOWNTIME_S
    energy_ok = (fz.P_SYS_KW / fz.P_NODE_KW) * tt < W
    not_c = tt < fz.CLASS_B_MAX_S
    if use_stoch:
        time_ok = t_cost < alpha * jnp.maximum(W + ppf_sigma, 0.0)
    else:
        time_ok = t_cost < alpha * W
    ok = time_ok & energy_ok & not_c
    rem = rem_ref[...]               # (1, bj)
    avoided = jnp.maximum(
        0.0, jnp.minimum(W, rem) - jnp.minimum(cur_green_ref[...], rem))
    benefit = (gamma * avoided
               - betaqp * (bq_load_ref[...] - load_src_ref[...]))
    benefit = benefit + free_pen_ref[...]
    sid = jax.lax.broadcasted_iota(jnp.int32, tt.shape, 0) + si * block_s
    valid = (ok
             & (sid != s_i_ref[...])
             & (benefit > jnp.maximum(t_cost, min_benefit_s)))
    b = jnp.where(valid, benefit, NEG_INF)
    mb_tile = b.max(axis=0, keepdims=True)
    tie = valid & (b == mb_tile)
    ttm = jnp.where(tie, tt, POS_INF)
    mtt_tile = ttm.min(axis=0, keepdims=True)
    tie = tie & (ttm == mtt_tile)
    dest_tile = jnp.where(tie, sid, BIG_IDX).min(axis=0, keepdims=True)

    mb_prev = mb_scr[...]
    mtt_prev = mtt_scr[...]
    # strict lexicographic improvement only: exact ties keep the earlier
    # (lower-sid) tile, matching global first-occurrence argmax
    better = (mb_tile > mb_prev) | ((mb_tile == mb_prev)
                                    & (mtt_tile < mtt_prev))
    mb_scr[...] = jnp.where(better, mb_tile, mb_prev)
    mtt_scr[...] = jnp.where(better, mtt_tile, mtt_prev)
    mdest_scr[...] = jnp.where(better, dest_tile, mdest_scr[...])

    @pl.when(si == n_s_blocks - 1)
    def _done():
        # no-valid rows never improved on the init state -> stay -1
        dest_ref[...] = mdest_scr[...]


def _blocks(K: int, S: int) -> Tuple[int, int, int]:
    """``(block_j, block_s, S_pad)`` for a padded batch: each block is
    either a whole axis or a tile-aligned slice (lanes: multiple of 128,
    sublanes: multiple of 8), so every padding bucket is a legal TPU
    tiling.  ``K`` is a power of two (:func:`pad_jobs`) and ``S`` a
    multiple of 8 (:func:`pad_sites`)."""
    block_j = min(K, _BLOCK_J)
    block_s = min(S, _BLOCK_S)
    return block_j, block_s, -(-S // block_s) * block_s


@functools.lru_cache(maxsize=64)
def _pallas_fn(B: int, K: int, S: int, alpha: float, gamma: float,
               betaqp: float, min_benefit_s: float, ppf_sigma: float,
               use_stoch: bool, interpret: bool):
    """Build + jit one pallas_call for a padded ``(B, S, K)`` batch
    (lru-cached so padding buckets, not raw job counts, bound the
    compile count)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_j, block_s, S_pad = _blocks(K, S)
    assert S_pad == S, (S, S_pad)
    n_j, n_s = K // block_j, S // block_s
    kernel = functools.partial(
        _dest_kernel, alpha=alpha, gamma=gamma, betaqp=betaqp,
        min_benefit_s=min_benefit_s, ppf_sigma=ppf_sigma,
        use_stoch=use_stoch, block_s=block_s, n_s_blocks=n_s)
    # leading batch dim squeezed: kernels see (1, bj) / (bs, 1) / (bs, bj)
    job_spec = pl.BlockSpec((None, 1, block_j), lambda b, j, s: (b, 0, j))
    site_spec = pl.BlockSpec((None, block_s, 1), lambda b, j, s: (b, s, 0))
    call = pl.pallas_call(
        kernel,
        grid=(B, n_j, n_s),
        in_specs=[pl.BlockSpec((None, block_s, block_j),
                               lambda b, j, s: (b, s, j)),
                  job_spec, job_spec, job_spec, job_spec, job_spec,
                  site_spec, site_spec, site_spec],
        out_specs=job_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, K), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, block_j), jnp.float32),
                        pltpu.VMEM((1, block_j), jnp.float32),
                        pltpu.VMEM((1, block_j), jnp.int32)],
        interpret=interpret,
        name="_dest_kernel",
    )
    return jax.jit(call)


def _pallas_inputs(batch: ScoreBatch, params: ScoreParams):
    """The kernel's build key (:func:`_pallas_fn` minus ``interpret``)
    and its host operands for one batch.

    ``t_transfer`` is computed here in float64 — the exact expression of
    :func:`_score_numpy` — and handed to the kernel rounded once to
    float32, so the kernel itself does no division (the TPU divides by a
    refined reciprocal) and checkpoint sizes of ~1e10 bytes never need
    to be exact in float32.  The rest is add / multiply / compare in
    float32.  The site axis is re-padded to the site tile, and the batch
    axis to the next power of two (so a sweep's drifting cell count
    reuses a few compiled shapes), with dead (``t_transfer = inf``)
    lanes."""
    B, K = batch.sizes.shape
    S = batch.bw.shape[2]
    _, _, S_pad = _blocks(K, S)
    B_pad = 1 << (B - 1).bit_length()
    f32 = np.float32
    tt = np.full((B_pad, S_pad, K), np.inf, f32)
    with np.errstate(divide="ignore"):
        tt[:B, :S, :] = 8.0 * batch.sizes[:, None, :] / np.swapaxes(
            batch.bw, 1, 2)

    def jobs(a, dtype=f32):
        out = np.zeros((B_pad, 1, K), dtype)
        out[:B, 0, :] = a
        return out

    def sites(a):
        out = np.zeros((B_pad, S_pad, 1), f32)
        out[:B, :S, 0] = a
        return out

    free_pen = np.where(batch.free_slots <= 0,
                        -params.queue_penalty_s, 0.0)
    key = (B_pad, K, S_pad, float(params.alpha), float(params.gamma),
           float(params.beta * params.queue_penalty_s),
           float(params.min_benefit_s), float(params.ppf_sigma),
           params.use_stoch)
    return key, [tt, jobs(batch.t_loads), jobs(batch.rem),
                 jobs(batch.cur_green), jobs(batch.load_src),
                 jobs(batch.s_i, np.int32), sites(batch.W),
                 sites(batch.bq_load), sites(free_pen)]


def _score_pallas(batch: ScoreBatch, params: ScoreParams) -> np.ndarray:
    """The tiled kernel in float32 (``interpret=True`` off-TPU)."""
    import jax
    import jax.numpy as jnp

    with telemetry.span("decide.batch"):
        key, args = _pallas_inputs(batch, params)
    _count_lanes(batch, args[0].size)
    with telemetry.span("decide.put"):
        args = [jnp.asarray(a) for a in args]
    with telemetry.span("decide.launch"):
        out = _pallas_fn(*key, jax.default_backend() != "tpu")(*args)
    with telemetry.span("decide.fetch"):
        out = np.asarray(out)
    return out[:batch.sizes.shape[0], 0, :]


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

_SCORE_FNS = {"numpy": _score_numpy, "jit": _score_jit,
              "pallas": _score_pallas}


def score_batch(batch: ScoreBatch, params: ScoreParams,
                backend_name: Optional[str] = None) -> np.ndarray:
    """Score a padded batch on the selected backend; ``(B, K)`` argbest
    destinations (``-1`` = stay put), padded job rows included."""
    return _SCORE_FNS[backend_name or backend()](batch, params)


def score_rows(rows: Sequence[StateRows], params: ScoreParams,
               backend_name: Optional[str] = None) -> List[np.ndarray]:
    """Batch + score many cells' rows in one fused pass; returns one
    un-padded ``(k_i,)`` destination array per cell."""
    if not rows:
        return []
    with telemetry.span("decide.batch"):
        batch = build_batch(rows)
    dest = score_batch(batch, params, backend_name)
    return [dest[b, :r.k] for b, r in enumerate(rows)]


__all__ = [
    "ScoreBatch", "ScoreParams", "StateRows", "backend", "build_batch",
    "pad_jobs", "pad_sites", "rows_from_state", "score_batch", "score_rows",
    "set_backend",
]
