#!/usr/bin/env python3
"""Bring-up check on one TPU chip.

Drives the system's main paths once, through the entry points a user
calls, and checks what comes out:

  (a) device  JAX must find a TPU; on anything else the script exits
      non-zero without a result line.
  (b) decide  ``ClusterSimulator`` on the default decide backend (the
      Pallas kernel, compiled for the chip): ``fleet-compiled``
      (100 sites x 10k jobs) and ``paper-table6`` reproduce their gated
      digits, the numpy oracle re-scores every kernel batch (0
      mismatches allowed), and a 100-seed slice of the batched sweep
      (many cells per kernel batch) equals a numpy-backend run.
  (c) pool    the process-pool mini-sweep finishes while this process
      holds the chip (its workers stay on the CPU).
  (d) jobs    ``micro-lm-100m`` at full width trains through
      ``repro.launch.train.main`` in ``full`` and ``int8`` checkpoint
      modes, moves to a second site directory with ``migrate_job`` and
      resumes there; ``full``-mode steps after the resume equal an
      uninterrupted run's.  ``repro.launch.serve.main`` then answers a
      batch of requests, checked against a teacher-forced forward pass.

Each phase also checks that the Pallas kernels it ran were lowered as
Mosaic TPU custom calls (compiled), not interpreted.  Wall times printed
along the way are set-up times of this run, compilation included, not
performance metrics.  The last line of a passing run is one JSON object:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage:  python3 chip_smoke.py
"""
from __future__ import annotations

import contextlib
import importlib
import io
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "micro-lm-100m"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    log(f"{'PASS' if ok else 'FAIL'} {what}")
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL {what}")


class Recorder:
    """Stands in for a jitted kernel entry point and keeps, per distinct
    call, what lowering that very function again needs (argument shapes
    and static options)."""

    def __init__(self, fn):
        self.fn = fn
        self.calls = {}

    def __call__(self, *args, **kw):
        import jax

        key = (tuple((a.shape, str(a.dtype)) for a in args),
               tuple(sorted(kw.items())))
        if key not in self.calls:
            self.calls[key] = (
                [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args], kw)
        return self.fn(*args, **kw)

    def compiled(self) -> bool:
        """Every recorded call lowers to a Mosaic TPU custom call."""
        return bool(self.calls) and all(
            "tpu_custom_call" in self.fn.lower(*shapes, **kw).as_text()
            for shapes, kw in self.calls.values())


def spy_module_fn(module, name: str) -> Recorder:
    rec = Recorder(getattr(module, name))
    setattr(module, name, rec)
    return rec


def run_entry(main, argv) -> str:
    """Run a launcher's ``main`` and return what it printed."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    out = buf.getvalue()
    check(rc == 0, f"{main.__module__}.main({' '.join(argv)}) returned {rc}")
    return out


# ---------------------------------------------------------------------------
# (b) the orchestrator's compiled decide path
# ---------------------------------------------------------------------------


def phase_decide(n_seeds: int = 100) -> None:
    import jax
    import numpy as np

    from benchmarks.run import FLEET_COMPILED_OVERRIDES, SWEEP_BATCHED_SPEC
    from repro.core import ClusterSimulator
    from repro.core import policy_kernels as pk
    from repro.core.sweep import SweepSpec, run_cells_batched

    backend = pk.backend()
    check(backend == "pallas",
          f"decide backend on {jax.default_backend()} is {backend!r}")

    kernels = {}  # _pallas_fn cache key -> Recorder of the jitted call
    build = pk._pallas_fn

    def recorded_build(*key):
        if key not in kernels:
            kernels[key] = Recorder(build(*key))
        return kernels[key]

    pk._pallas_fn = recorded_build

    parity = dict(batches=0, rows=0, mismatched=0, max_b=0, max_k=0)
    score = pk._SCORE_FNS["pallas"]

    def scored_and_checked(batch, params):
        got = score(batch, params)
        want = pk._score_numpy(batch, params)
        B, K = batch.sizes.shape
        parity["batches"] += 1
        parity["rows"] += sum(batch.n_jobs)
        parity["mismatched"] += int((got != want).sum())
        parity["max_b"] = max(parity["max_b"], B)
        parity["max_k"] = max(parity["max_k"], K)
        return got

    pk._SCORE_FNS["pallas"] = scored_and_checked

    rows = [("paper-table6", "paper-table6", None, (240, 480, 244.6)),
            ("fleet-compiled", "forecastable-brownouts",
             FLEET_COMPILED_OVERRIDES, (10000, 12909, 19376.4))]
    for label, scenario, overrides, want in rows:
        t0 = time.perf_counter()
        r = ClusterSimulator.from_scenario(
            scenario, "feasibility-aware", overrides=overrides).run()
        got = (r.completed, r.migrations, round(r.grid_kwh, 1))
        log(f"{label}: completed={got[0]} migrations={got[1]} "
            f"grid_kwh={got[2]} (set-up + run wall "
            f"{time.perf_counter() - t0:.1f}s)")
        check(got == want, f"{label} reproduces completed/migrations/"
                           f"grid_kwh {want}")

    spec = SweepSpec(**{**SWEEP_BATCHED_SPEC, "seeds": tuple(range(n_seeds))})
    t0 = time.perf_counter()
    on_chip = run_cells_batched(spec.cells(keep_results=False),
                                keep_results=False)
    log(f"batched sweep on {backend}: {len(on_chip.runs)} runs "
        f"(set-up + run wall {time.perf_counter() - t0:.1f}s)")
    pk.set_backend("numpy")
    try:
        oracle = run_cells_batched(spec.cells(keep_results=False),
                                   keep_results=False)
    finally:
        pk.set_backend(None)
    done = [r.summary["completed"] for r in on_chip.runs]
    want = [r.summary["completed"] for r in oracle.runs]
    log(f"batched sweep completions: {sum(done)} on {backend}, "
        f"{sum(want)} on numpy")
    check(done == want, "batched-sweep completions equal a numpy-backend run")
    check(on_chip.deterministic_summaries() == oracle.deterministic_summaries(),
          "batched-sweep summaries equal a numpy-backend run")

    log(f"decide kernel batches: {parity['batches']} "
        f"({parity['rows']} job rows, largest batch B={parity['max_b']} "
        f"K={parity['max_k']}), destination mismatches vs numpy: "
        f"{parity['mismatched']}")
    check(parity["max_b"] > 1, "batches with B > 1 reached the kernel")
    check(parity["mismatched"] == 0,
          "Pallas destinations equal _score_numpy's on every batch")
    interpret = sorted({key[-1] for key in kernels})
    log(f"decide kernel shapes compiled: "
        f"{sorted({key[:3] for key in kernels})}, interpret={interpret}")
    check(interpret == [False] and all(r.compiled() for r in kernels.values()),
          "decide kernel ran compiled (Mosaic custom call), not interpreted")
    pk._pallas_fn = build
    pk._SCORE_FNS["pallas"] = score


# ---------------------------------------------------------------------------
# (c) the process pool while this process holds the chip
# ---------------------------------------------------------------------------


def phase_pool() -> None:
    from benchmarks.run import MINI_SWEEP_SPEC
    from repro.core.sweep import SweepSpec, run_cells

    spec = SweepSpec(**MINI_SWEEP_SPEC)
    t0 = time.perf_counter()
    sw = run_cells(spec.cells(keep_results=False), workers=2,
                   keep_results=False)
    completed = sum(r.summary["completed"] for r in sw.runs)
    log(f"run_cells(workers={sw.workers}): {len(sw.runs)} runs, "
        f"completed={completed} (wall {time.perf_counter() - t0:.1f}s)")
    check(sw.workers == 2 and completed == 8 * spec.overrides["n_jobs"],
          "process-pool mini-sweep finished after the chip was held")


# ---------------------------------------------------------------------------
# (d) the job substrate: train, checkpoint, migrate, resume, serve
# ---------------------------------------------------------------------------


def _losses(out: str):
    """{step: loss} from the history rows ``repro.launch.train`` prints."""
    rows = [json.loads(line) for line in out.splitlines()
            if line.strip().startswith("{")]
    return {row["step"]: row["loss"] for row in rows}


def phase_jobs(arch: str = ARCH, steps: int = 6, split: int = 3,
               batch: int = 8, seq: int = 256, serve_tokens: int = 16) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config
    from repro.core.migration import migrate_job
    # the package exports a function named flash_attention: import the
    # kernel modules by their full names
    flash_attention = importlib.import_module("repro.kernels.flash_attention")
    quantize = importlib.import_module("repro.kernels.quantize")
    from repro.launch import serve, train
    from repro.models.model import build_model

    attn = spy_module_fn(flash_attention, "flash_attention_pallas")
    quant = spy_module_fn(quantize, "quantize_int8_pallas")
    dequant = spy_module_fn(quantize, "dequantize_int8_pallas")
    cfg = get_config(arch)
    common = ["--arch", arch, "--steps", str(steps), "--batch", str(batch),
              "--seq", str(seq), "--log-every", "1",
              "--save-every", str(steps)]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        t0 = time.perf_counter()
        ref = _losses(run_entry(
            train.main, common + ["--ckpt-dir", os.path.join(tmp, "ref")]))
        shutil.rmtree(os.path.join(tmp, "ref"))
        log(f"{arch} uninterrupted: {steps} steps, losses "
            f"{[ref[s] for s in sorted(ref)]} (set-up + run wall "
            f"{time.perf_counter() - t0:.1f}s)")
        check(sorted(ref) == list(range(1, steps + 1))
              and all(math.isfinite(v) for v in ref.values()),
              f"{arch} trains {steps} steps with finite loss")
        for mode in ("full", "int8"):
            site_a = os.path.join(tmp, f"{mode}-site-a")
            site_b = os.path.join(tmp, f"{mode}-site-b")
            first = _losses(run_entry(train.main, common + [
                "--ckpt-mode", mode, "--ckpt-dir", site_a,
                "--max-steps", str(split)]))
            src = CheckpointManager(site_a, job=cfg.name, mode=mode)
            _, report = migrate_job(src, site_b)
            shutil.rmtree(site_a)
            out = run_entry(train.main, common + [
                "--ckpt-mode", mode, "--ckpt-dir", site_b, "--resume"])
            shutil.rmtree(site_b)
            second = _losses(out)
            log(f"{mode}: {split} steps at site A, migrate_job step "
                f"{report.step} ({report.nbytes} bytes), resumed at site B "
                f"for steps {sorted(second)}: losses "
                f"{[second[s] for s in sorted(second)]}")
            check(f"resumed from step {split}" in out
                  and sorted(second) == list(range(split + 1, steps + 1))
                  and all(math.isfinite(v) for v in second.values()),
                  f"{mode}: resumed at site B with finite loss")
            check(all(first[s] == ref[s] for s in range(1, split + 1)),
                  f"{mode}: steps before the save equal the uninterrupted run")
            if mode == "full":
                check(all(second[s] == ref[s] for s in second),
                      "full: steps after migrate + restore equal the "
                      "uninterrupted run exactly")
            else:
                gap = max(abs(second[s] - ref[s]) for s in second)
                log(f"int8: largest loss gap to the uninterrupted run after "
                    f"the lossy restore: {gap}")
        check(attn.compiled() and quant.compiled() and dequant.compiled()
              and all(not kw.get("interpret", False)
                      for rec in (attn, quant, dequant)
                      for _, kw in rec.calls.values()),
              f"flash attention ({len(attn.calls)} shapes), int8 quantize "
              f"({len(quant.calls)}) and dequantize ({len(dequant.calls)}) "
              f"ran compiled, not interpreted")

    prompt_len, n_req = 8, 4
    out = run_entry(serve.main, [
        "--arch", arch, "--batch", str(n_req), "--prompt-len",
        str(prompt_len), "--tokens", str(serve_tokens)])
    lines = [ln for ln in out.splitlines() if ln.startswith("[serve]")]
    for ln in lines:
        log(ln)
    sample = json.loads(lines[-1].split("sample:", 1)[1])
    check(len(sample) == prompt_len + serve_tokens
          and all(0 <= t < cfg.vocab_size for t in sample),
          f"serve answered {n_req} requests with {serve_tokens} tokens each")
    # reference: a teacher-forced forward pass over the served sequence
    # (the Pallas attention path) must rank every token the KV-cache
    # decode chose (the reference-attention path) among its top 5
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))  # serve's weights
    logits, _ = jax.jit(model.forward)(
        params, {"tokens": jnp.asarray([sample], jnp.int32)})
    logits = np.asarray(logits[0], np.float32)
    ranks = [int((logits[i] > logits[i, sample[i + 1]]).sum())
             for i in range(prompt_len - 1, len(sample) - 1)]
    log(f"serve: rank of each decoded token under the forward pass: {ranks}")
    check(max(ranks) < 5, "decoded tokens agree with the forward pass")


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: no src/repro next to this script; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compilation cache: {enable_compile_cache()}")
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 1
    for name, phase in (("decide", phase_decide), ("pool", phase_pool),
                        ("jobs", phase_jobs)):
        t0 = time.perf_counter()
        log(f"phase {name} ...")
        phase()
        log(f"phase {name} passed ({time.perf_counter() - t0:.1f}s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
