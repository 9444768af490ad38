"""The program's spans and counters (``repro.telemetry``): off by
default, nested with self times, one session at a time, on the
profiler's host timeline, and placed where the decide path and the
checkpoint path do their work."""
import glob
import os
from collections import Counter

import jax
import pytest

from repro import telemetry
from repro.core import policy_kernels as pk

DECIDE_CHILDREN = {"decide.prep", "decide.batch", "decide.put",
                   "decide.launch", "decide.fetch", "decide.commit"}
CKPT_SPANS = {"ckpt.save", "ckpt.save.gather", "ckpt.save.encode",
              "ckpt.save.write", "ckpt.restore", "ckpt.restore.read",
              "ckpt.restore.decode", "train.restore_template"}


def test_nothing_is_recorded_with_the_profiler_off(monkeypatch):
    made = []

    class Counted(telemetry.TraceAnnotation):
        def __init__(self, *a, **k):
            made.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(telemetry, "TraceAnnotation", Counted)
    with telemetry.recording():
        with telemetry.span("seed"):
            pass
    made.clear()
    assert not Counted.is_enabled()
    a, b = telemetry.span("x"), telemetry.span("y")
    assert a is b  # one shared no-op, nothing allocated per span
    with a:
        with telemetry.span("inner"):
            telemetry.count("n", 5)
    assert made == [] and not telemetry.active()
    assert [s.name for s in telemetry.spans()] == ["seed"]
    assert "n" not in telemetry.counters()


def test_nested_spans_link_parents_and_self_times():
    with telemetry.recording():
        with telemetry.span("outer"):
            with telemetry.span("a"):
                with telemetry.span("a.leaf"):
                    pass
            with telemetry.span("b"):
                pass
        with telemetry.span("top"):
            telemetry.count("things", 2)
            telemetry.count("things")
    got = telemetry.spans()
    assert [(s.name, s.parent) for s in got] == [
        ("outer", -1), ("a", 0), ("a.leaf", 1), ("b", 0), ("top", -1)]
    outer, a, leaf, b, _ = got
    assert outer.start_ns <= a.start_ns <= leaf.start_ns <= leaf.end_ns \
        <= a.end_ns <= b.start_ns <= b.end_ns <= outer.end_ns
    assert outer.child_ns == a.dur_ns + b.dur_ns
    assert outer.self_ns == outer.dur_ns - a.dur_ns - b.dur_ns
    assert a.self_ns == a.dur_ns - leaf.dur_ns
    assert leaf.self_ns == leaf.dur_ns
    assert telemetry.counters() == {"things": 3}


def test_a_new_session_clears_and_the_cap_counts_dropped(monkeypatch):
    with telemetry.recording():
        with telemetry.span("first"):
            telemetry.count("c")
    with telemetry.recording():
        with telemetry.span("second"):
            pass
    assert [s.name for s in telemetry.spans()] == ["second"]
    assert telemetry.counters() == {}
    monkeypatch.setattr(telemetry, "CAP", 3)
    with telemetry.recording():
        with telemetry.span("p"):
            for _ in range(4):
                with telemetry.span("c"):
                    pass
    got = telemetry.spans()
    assert [s.name for s in got] == ["p", "c", "c"]
    assert got[0].child_ns == got[1].dur_ns + got[2].dur_ns
    assert telemetry.counters()["dropped"] == 2


@pytest.fixture
def pallas(monkeypatch):
    """The Pallas decide backend (interpreted on the CPU); yields the
    batches of its kernel calls."""
    calls = []
    score = pk._SCORE_FNS["pallas"]

    def counted(batch, params):
        calls.append(batch)
        return score(batch, params)

    monkeypatch.setitem(pk._SCORE_FNS, "pallas", counted)
    pk.set_backend("pallas")
    yield calls
    pk.set_backend(None)


def test_decide_spans_reach_the_profilers_host_timeline(tmp_path, pallas):
    from jax.profiler import ProfileData

    from repro.core.orchestrator import FeasibilityAwarePolicy
    from tests.test_vectorized import random_state

    states = [random_state(s) for s in range(6)]
    pol = FeasibilityAwarePolicy()
    states = [s for s in states if pol._prep(s) is not None][:2]
    pol.decide(states[0])  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for s in states:
            pol.decide(s)
    names = [s.name for s in telemetry.spans()]
    assert names.count("decide") == 2
    assert names.count("decide.fetch") == 2
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    host = [ev for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro:")]
    decide = [e for e in host if e.name == "repro:decide"]
    assert len(decide) == 2
    for child in ("repro:decide.put", "repro:decide.fetch"):
        inner = [e for e in host if e.name == child]
        assert len(inner) == 2
        for e in inner:
            assert any(d.start_ns <= e.start_ns and e.start_ns + e.duration_ns
                       <= d.start_ns + d.duration_ns for d in decide)
    # the session is over: the next untraced decide records nothing
    pol.decide(states[0])
    assert len(telemetry.spans()) == len(names)


def test_episode_on_the_pallas_backend_records_every_decide(pallas):
    from repro.core import ClusterSimulator

    sim = ClusterSimulator.from_scenario(
        "paper-table6", "feasibility-aware",
        overrides=dict(days=1, n_jobs=30, seed=3))
    decides = []
    decide = sim.policy.decide

    def counted(state):
        decides.append(state.t)
        return decide(state)

    sim.policy.decide = counted
    with telemetry.recording():
        sim.run()
    got = telemetry.spans()
    names = Counter(s.name for s in got)
    assert pallas and names["decide"] == len(decides)
    for stage in ("decide.put", "decide.launch", "decide.fetch"):
        assert names[stage] == len(pallas)
    assert set(names) == {"decide"} | DECIDE_CHILDREN
    for s in got:
        assert s.end_ns >= s.start_ns
        if s.name == "decide":
            assert 0 <= s.child_ns <= s.dur_ns
        else:
            parent = got[s.parent]
            assert parent.name == "decide"
            assert s.dur_ns <= parent.dur_ns
    c = telemetry.counters()
    assert c["decide.rows"] == sum(
        k * n for b in pallas for k, n in zip(b.n_jobs, b.n_sites))
    assert 0 < c["decide.rows"] <= c["decide.lanes"]


def test_save_migrate_restore_records_every_checkpoint_stage(tmp_path):
    from repro.core.migration import migrate_job
    from tests.test_system import make_trainer

    a = make_trainer(tmp_path, site="A", steps=4)
    a.run(max_steps=2)
    with telemetry.recording():
        a.save()
        dst, report = migrate_job(a.ckpt, str(tmp_path / "B"))
        b = make_trainer(tmp_path, site="B", steps=4)
        b.ckpt = dst
        assert b.restore() == 2
    got = telemetry.spans()
    assert {s.name for s in got} == CKPT_SPANS
    by = {s.name: s for s in got}
    for child, parent in [("ckpt.save.gather", "ckpt.save"),
                          ("ckpt.save.encode", "ckpt.save"),
                          ("ckpt.save.write", "ckpt.save"),
                          ("ckpt.restore.read", "ckpt.restore"),
                          ("ckpt.restore.decode", "ckpt.restore")]:
        assert got[by[child].parent].name == parent
    for top in ("ckpt.save", "ckpt.restore", "train.restore_template"):
        assert by[top].parent == -1
    # migrate_job records nothing of its own: its export and import lie
    # between the save and the restore
    assert by["ckpt.save"].end_ns <= by["train.restore_template"].start_ns
    assert 0 < report.t_serialize_s < (by["train.restore_template"].start_ns
                                       - by["ckpt.save"].end_ns) * 1e-9
    assert telemetry.counters() == {}


def test_async_save_records_the_writer_threads_stages_at_its_top(tmp_path):
    from repro.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), async_save=True)
    with telemetry.recording():
        info = mgr.save(5, {"w": jax.numpy.ones((4, 4))})
        mgr.wait()
    got = {s.name: s for s in telemetry.spans()}
    assert set(got) == {"ckpt.save", "ckpt.save.gather", "ckpt.save.encode",
                        "ckpt.save.write"}
    assert got["ckpt.save.gather"].parent >= 0
    # the writer thread opens its stages with no enclosing span of its own
    assert got["ckpt.save.encode"].parent == got["ckpt.save.write"].parent == -1
    assert got["ckpt.save.encode"].end_ns <= got["ckpt.save.write"].start_ns
    assert info.nbytes > 0
