"""The reader of ``ckpt_direct_share``: on counters made by hand, with
nothing counted, on a program without the module, and in traced runs of
the small migration cell on the CPU, in its full and int8 variants."""
import sys

import pytest

import conftest
from harness import program_spans, spec
from harness.runner import run_cell


def read():
    return spec.reader("ckpt_direct_share").read(None)


def test_reads_the_direct_bytes_over_all(monkeypatch):
    monkeypatch.setattr(program_spans, "registry", lambda: (
        [], {"ckpt.bytes": 400, "ckpt.bytes_direct": 300}))
    assert read() == pytest.approx(75.0)


def test_nothing_counted_reads_none(monkeypatch):
    monkeypatch.setattr(program_spans, "registry", lambda: ([], {}))
    assert read() is None


def test_a_program_without_telemetry_reads_none(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "telemetry", raising=False)
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert read() is None


@pytest.mark.parametrize("variant", [None, "control"])
def test_traced_migration_reads_the_share(small_root, variant):
    from repro import telemetry

    # a check with no trace running ends the session an earlier traced
    # run of this process left, as a new process starts with none
    assert not telemetry.active()
    line = run_cell("small-smollm2-migrate", seed=2 ** 31 + 9, seconds=0.3,
                    trace=True, root=small_root, require_tpu=False,
                    peak=conftest.CPU_PEAK, variant=variant)
    got = line["metrics"]["ckpt_direct_share"]["value"]
    if variant is None:
        assert got == 100.0
    else:  # int8 blobs are staged; only the 4-byte step is raw
        assert 0 < got < 0.1
