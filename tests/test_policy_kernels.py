"""Parity of the batched/compiled decide kernels (policy_kernels).

The contract under test: for arbitrary cluster states, the cross-cell
batched kernel path produces exactly what the per-cell numpy grids
produce, which in turn produce exactly what the per-job scalar oracle
(``decide_scalar``) produces — one chain of bit-identical Action lists,
with the padded batch lanes never leaking into a real row's verdict.

Runs as a seeded property-style suite; when hypothesis is installed the
same properties also run under its generator.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # clean environments: deterministic tests still run
    HAS_HYPOTHESIS = False

from repro.core import policy_kernels as pk
from repro.core.orchestrator import FeasibilityAwarePolicy, score_migrations
from repro.core.state import ClusterState, JobView, SiteView
from tests.test_vectorized import random_state

GB = 1e9
HOUR = 3600.0

PARAM_SETS = [
    dict(),
    dict(min_benefit_s=0.0),
    dict(eps=0.05, forecast_sigma_s=900.0),
]


def _cells(seed, n_cells):
    """A batch of random cells with their candidate rows (live cells
    only, mirroring what ``decide_batch`` feeds ``score_states``)."""
    pol = FeasibilityAwarePolicy()
    states, cands = [], []
    for i in range(n_cells):
        s = random_state(seed * 101 + i)
        c = pol._prep(s)
        if c is not None:
            states.append(s)
            cands.append(c)
    return states, cands


@pytest.mark.parametrize("seed", range(12))
def test_batch_from_states_matches_per_cell_rows(seed):
    """The one-pass cross-cell gather builds the exact ScoreBatch of the
    per-cell rows_from_state + build_batch path."""
    states, cands = _cells(seed, 5)
    if not states:
        pytest.skip("no live cells at this seed")
    got = pk.batch_from_states(states, cands)
    want = pk.build_batch(
        [pk.rows_from_state(s, c) for s, c in zip(states, cands)])
    assert got.n_jobs == want.n_jobs and got.n_sites == want.n_sites
    for f in ("sizes", "t_loads", "rem", "s_i", "cur_green", "load_src",
              "bw", "W", "bq_load", "free_slots"):
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f), f)


@pytest.mark.parametrize("kwargs", PARAM_SETS)
@pytest.mark.parametrize("seed", range(12))
def test_score_states_matches_per_cell_score_migrations(seed, kwargs):
    """Batched multi-cell dests == per-cell fused numpy grids."""
    pol = FeasibilityAwarePolicy(**kwargs)
    states, cands = _cells(seed, 5)
    if not states:
        pytest.skip("no live cells at this seed")
    dests = pk.score_states(states, cands, pol._params())
    for s, c, got in zip(states, cands, dests):
        _, _, want = score_migrations(
            s, c, s.bandwidth_bps[s.soa.site[c], :], alpha=pol.alpha,
            eps=pol.eps, forecast_sigma_s=pol.forecast_sigma_s,
            gamma=pol.gamma, beta=pol.beta,
            queue_penalty_s=pol.queue_penalty_s,
            min_benefit_s=pol.min_benefit_s)
        if want is None:
            assert got is None or not (np.asarray(got) >= 0).any()
        else:
            assert got is not None
            np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("backend", ["jit", "pallas"])
@pytest.mark.parametrize("seed", range(12))
def test_compiled_backends_match_numpy_dest(seed, backend):
    """jit (float64 XLA) and pallas (tiled, interpret off-TPU) resolve
    the same argbest destinations as the numpy oracle."""
    states, cands = _cells(seed, 4)
    if not states:
        pytest.skip("no live cells at this seed")
    params = FeasibilityAwarePolicy()._params()
    batch = pk.batch_from_states(states, cands)
    want = pk.score_batch(batch, params, "numpy")
    got = pk.score_batch(batch, params, backend)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("backend", ["jit", "pallas"])
@pytest.mark.parametrize("seed", range(10))
def test_backend_decide_matches_scalar_oracle(seed, backend):
    """End-to-end: Policy.decide under a compiled backend emits the
    bit-identical Action list of decide_scalar (reservation walk
    included)."""
    state = random_state(seed)
    pol = FeasibilityAwarePolicy()
    want = pol.decide_scalar(state)
    pk.set_backend(backend)
    try:
        got = pol.decide(state)
    finally:
        pk.set_backend(None)
    assert got == want


@pytest.mark.parametrize("seed", range(10))
def test_decide_batch_matches_per_cell_decide(seed):
    """The sweep runner's entry point: one fused pass over many cells
    == per-cell decide == per-cell decide_scalar."""
    pol = FeasibilityAwarePolicy()
    states = [random_state(seed * 31 + i) for i in range(6)]
    got = pol.decide_batch(states)
    assert got == [pol.decide(s) for s in states]
    assert got == [pol.decide_scalar(s) for s in states]


# ---------------------------------------------------------------------------
# padded-lane edge cases
# ---------------------------------------------------------------------------


def _mini_state(n_sites, jobs, t=1.0 * HOUR, green=None):
    sites = [
        SiteView(sid=s, slots=4, busy=1, queued=0,
                 renewable_active=bool(green[s]) if green else False,
                 window_remaining_s=6.0 * HOUR if green and green[s] else 0.0,
                 incoming=0, next_window_start_s=t + 2 * HOUR)
        for s in range(n_sites)
    ]
    return ClusterState.build(t, jobs, sites, nic_bps=2e9)


def test_all_dark_tick_short_circuits():
    """No positive window anywhere: _prep bails before any kernel work
    and decide returns no actions on every backend."""
    jobs = [JobView(jid=0, site=0, ckpt_bytes=10 * GB,
                    remaining_compute_s=4 * HOUR, state="running")]
    state = _mini_state(3, jobs)
    pol = FeasibilityAwarePolicy()
    assert pol._prep(state) is None
    for backend in ("numpy", "jit", "pallas"):
        pk.set_backend(backend)
        try:
            assert pol.decide(state) == []
        finally:
            pk.set_backend(None)
    assert pol.decide_scalar(state) == []


def test_zero_feasible_destinations_returns_none_cell():
    """A live cell whose rows all fail feasibility yields a None dest
    list entry (the batched no-migration fast path), and an empty
    Action list end to end."""
    # green destination exists but the checkpoint is far too large to
    # move inside any window at nic_bps=2e9
    jobs = [JobView(jid=0, site=0, ckpt_bytes=4000 * GB,
                    remaining_compute_s=12 * HOUR, state="running")]
    state = _mini_state(3, jobs, green=[False, True, False])
    pol = FeasibilityAwarePolicy()
    cand = pol._prep(state)
    assert cand is not None
    dests = pk.score_states([state], [cand], pol._params())
    assert dests == [None]
    assert pol.decide(state) == [] == pol.decide_scalar(state)


def test_single_job_cells_batch():
    """k=1 cells pad up to the minimum job bucket; the padded rows must
    never surface as actions."""
    pol = FeasibilityAwarePolicy()
    states = []
    for i in range(4):
        jobs = [JobView(jid=7, site=0, ckpt_bytes=(5 + i) * GB,
                        remaining_compute_s=8 * HOUR, state="running")]
        states.append(_mini_state(3, jobs, green=[False, True, i % 2 == 0]))
    got = pol.decide_batch(states)
    assert got == [pol.decide_scalar(s) for s in states]
    assert all(len(acts) <= 1 for acts in got)
    assert any(got)  # the setup admits at least one migration


def test_padding_buckets_reuse_shapes():
    """Job-count drift inside one power-of-two bucket must not change
    the padded shape (the no-recompile guarantee)."""
    assert pk.pad_jobs(1) == pk.pad_jobs(8) == 8
    assert pk.pad_jobs(9) == pk.pad_jobs(16) == 16
    assert pk.pad_sites(3) == pk.pad_sites(8) == 8
    assert pk.pad_sites(9) == 16


if HAS_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 10_000))
    def test_decide_batch_matches_scalar_hypothesis(seed):
        pol = FeasibilityAwarePolicy()
        states = [random_state(seed * 17 + i) for i in range(4)]
        assert pol.decide_batch(states) == [
            pol.decide_scalar(s) for s in states]


@pytest.mark.parametrize("seed", range(4))
def test_pallas_multi_tile_matches_numpy(monkeypatch, seed):
    """With tiles smaller than the batch (several job tiles on lanes,
    several site tiles on sublanes), the running argbest across site
    tiles and the dead padded lanes still give numpy's destinations —
    ties included (sites share windows, loads and bandwidth)."""
    monkeypatch.setattr(pk, "_BLOCK_J", 128)
    monkeypatch.setattr(pk, "_BLOCK_S", 8)
    pk._pallas_fn.cache_clear()
    rng = np.random.default_rng(seed)
    B, k, n = 3, 300, 21
    rows = [pk.StateRows(
        sizes=rng.choice([5.0, 20.0, 60.0], k) * GB,
        t_loads=np.full(k, 10.3), rem=rng.uniform(0, 12, k) * HOUR,
        cur_green=np.where(rng.random(k) < 0.3, 2 * HOUR, 0.0),
        load_src=rng.choice([0.25, 0.5, 1.0], k),
        s_i=rng.integers(0, n, k),
        bw=rng.choice([0.0, 1e9, 10e9], (k, n)),
        W=rng.choice([0.0, 3 * HOUR, 6 * HOUR], n),
        bq_load=rng.choice([0.0, 0.25, 0.5], n),
        free_slots=rng.integers(-1, 3, n)) for _ in range(B)]
    batch = pk.build_batch(rows)
    params = FeasibilityAwarePolicy()._params()
    want = pk.score_batch(batch, params, "numpy")
    try:
        got = pk.score_batch(batch, params, "pallas")
    finally:
        pk._pallas_fn.cache_clear()
    assert (want >= 0).any()
    np.testing.assert_array_equal(got, want)
