"""The readers of the program's own spans and counters: on a registry
made by hand, with nothing recorded, on a program without the module,
and in traced runs of the small cells on the CPU, where they have to add
up to what the benchmark's own spans read."""
import sys

import pytest

import conftest
from harness import program_spans, spec
from harness.runner import run_cell
from repro.telemetry import Span

FLEET = ("decide_host_ms", "decide_roundtrip_ms", "decide_useful_lanes")
CKPT = ("save_gather_s", "save_encode_s", "save_write_s", "restore_init_s",
        "restore_load_s")
US, S = 1000, 10 ** 9  # nanoseconds


def read(name):
    return spec.reader(name).read(None)


def registry(monkeypatch, spans, counters):
    monkeypatch.setattr(program_spans, "registry", lambda: (spans, counters))


def tree(rows):
    """Spans from ``(name, parent, start, end)``, with the time each
    one's direct children cover filled in."""
    child = [0] * len(rows)
    for _, p, a, b in rows:
        if p >= 0:
            child[p] += b - a
    return [Span(n, p, a, b, c) for (n, p, a, b), c in zip(rows, child)]


def test_decide_readers(monkeypatch):
    spans = tree([
        ("decide", -1, 0, 4000 * US),
        ("decide.prep", 0, 0, 500 * US),
        ("decide.batch", 0, 500 * US, 900 * US),
        ("decide.put", 0, 900 * US, 1200 * US),
        ("decide.launch", 0, 1200 * US, 1500 * US),
        ("decide.fetch", 0, 1500 * US, 3500 * US),
        ("decide.commit", 0, 3500 * US, 3900 * US),
        ("decide", -1, 5000 * US, 5100 * US),  # a tick with no candidates
        ("decide.prep", 7, 5000 * US, 5090 * US),
        ("decide.put", -1, 6000 * US, 7000 * US),  # outside any decide
    ])
    registry(monkeypatch, spans, {"decide.rows": 30, "decide.lanes": 120})
    assert read("decide_roundtrip_ms") == pytest.approx(2.6 / 2)
    assert read("decide_host_ms") == pytest.approx((4.0 - 2.6 + 0.1) / 2)
    assert read("decide_useful_lanes") == pytest.approx(25.0)


def test_checkpoint_readers(monkeypatch):
    spans = tree([
        ("ckpt.save", -1, 0, 10 * S),
        ("ckpt.save.gather", 0, 0, 4 * S),
        ("ckpt.save.encode", 0, 4 * S, 7 * S),
        ("ckpt.save.write", 0, 7 * S, 9 * S),
        ("ckpt.save", -1, 20 * S, 26 * S),
        ("ckpt.save.gather", 4, 20 * S, 22 * S),
        ("ckpt.save.encode", 4, 22 * S, 23 * S),
        ("ckpt.save.write", 4, 23 * S, 26 * S),
        ("other", -1, 30 * S, 32 * S),  # read by none of these
        ("train.restore_template", -1, 40 * S, 43 * S),
        ("ckpt.restore", -1, 43 * S, 48 * S),
        ("ckpt.restore.read", 10, 43 * S, 45 * S),
        ("ckpt.restore.decode", 10, 45 * S, 47 * S),
        ("ckpt.save.write", -1, 50 * S, 0),  # still open: left out
    ])
    registry(monkeypatch, spans, {})
    assert read("save_gather_s") == pytest.approx(3.0)
    assert read("save_encode_s") == pytest.approx(2.0)
    assert read("save_write_s") == pytest.approx(2.5)
    assert read("restore_init_s") == pytest.approx(3.0)
    assert read("restore_load_s") == pytest.approx(4.0)
    assert read("decide_host_ms") is None


def test_nothing_recorded_reads_none(monkeypatch):
    registry(monkeypatch, [], {})
    assert [read(m) for m in FLEET + CKPT] == [None] * 8


def test_a_program_without_telemetry_reads_none(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert program_spans.registry() is None
    assert [read(m) for m in FLEET + CKPT] == [None] * 8


def traced(root, cell):
    return run_cell(cell, seed=2 ** 31 + 5, seconds=0.3, trace=True,
                    root=root, require_tpu=False, peak=conftest.CPU_PEAK)


def test_traced_fleet_reads_the_decide_split(small_root, decide_backend):
    from repro import telemetry

    line = traced(small_root, "small-fleet-paper")
    assert line["correct"]
    got = {m: line["metrics"][m]["value"] for m in FLEET}
    calls = [s for s in telemetry.spans() if s.name == "decide"]
    mean_ms = sum(s.dur_ns for s in calls) * 1e-6 / len(calls)
    assert got["decide_host_ms"] + got["decide_roundtrip_ms"] == \
        pytest.approx(mean_ms)
    assert 0 < got["decide_useful_lanes"] <= 100


def test_traced_migration_reads_every_checkpoint_stage(small_root):
    line = traced(small_root, "small-smollm2-migrate")
    assert line["correct"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(m[k] > 0 for k in CKPT)
    save = m["save_gather_s"] + m["save_encode_s"] + m["save_write_s"]
    assert save <= m["save_s"]
    assert m["restore_init_s"] + m["restore_load_s"] <= m["restore_s"]
