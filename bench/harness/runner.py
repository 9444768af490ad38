"""One run of one cell: set-up, the measured window, the check of
correctness, and (with tracing) the per-layer metrics.

A window driver (``drivers/<name>.py``) provides four functions, each
taking the run's :class:`Context`:

``setup(ctx)``    builds the system under test from the seed and warms
                  every shape its window uses; returns the driver state.
``window(ctx, st)`` drives the timed path for ``ctx.seconds`` and
                  returns ``{end-to-end metric: value}``.
``check(ctx, st)``  frees the program's state and compares what the
                  window produced with the configuration's reference;
                  returns ``(attempted, failed, [Check, ...])``.
``close(ctx, st)``  removes whatever the run wrote.

``ctx.plant(point, obj)`` is called by a driver at each named point of
its timed path and returns the object to use; it is the identity in a
benchmark run, and lets the tests break the timed path underneath.
"""
from __future__ import annotations

import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from harness import device as dev
from harness import spec
from harness import trace as tr
from harness.probe import CompileCounter, Probe


def finite(x: float) -> float:
    """``x``, or the largest float where it is not finite: a result line
    is strict JSON, which has no infinity."""
    import math

    return float(x) if math.isfinite(x) else 1.7976931348623157e308


@dataclass
class Check:
    name: str
    value: float
    limit: float
    upper: bool = True  # the value must not exceed the limit

    @property
    def ok(self) -> bool:
        return self.value <= self.limit if self.upper else self.value >= self.limit


@dataclass
class Context:
    cell: spec.Cell
    seed: int
    seconds: float
    traced: bool
    workdir: str
    probe: Probe = field(default_factory=Probe)
    variant: Optional[str] = None  # None, or "control"
    plant: Callable[[str, Any], Any] = lambda point, obj: obj
    peak: Dict[str, float] = field(default_factory=dict)
    trace: Optional[tr.Reduced] = None
    info: Dict[str, Any] = field(default_factory=dict)

    @property
    def config(self) -> Dict[str, Any]:
        return self.cell.config

    @property
    def traffic(self) -> Dict[str, Any]:
        return self.cell.traffic

    def subseed(self, *tags: int) -> int:
        """A 31-bit seed derived from the run's seed and ``tags``."""
        import numpy as np

        ss = np.random.SeedSequence([self.seed, *tags])
        return int(ss.generate_state(1)[0] >> 1)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``, caching every program, so that only a
    checkout's first run of a cell compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _log(msg: str) -> None:
    import sys

    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             t_start: Optional[float] = None, root: str = spec.ROOT,
             require_tpu: bool = True, peak: Optional[Dict[str, float]] = None,
             variant: Optional[str] = None,
             plant: Optional[Callable[[str, Any], Any]] = None,
             ) -> Dict[str, Any]:
    """Run ``workload`` once and return its result line as a dict.
    Raises :class:`device.NoAccelerator` before any work where the chip
    is missing (tests pass ``require_tpu=False`` and a ``peak``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.resolve(workload, root)
    cache = enable_compile_cache(root)
    import jax

    if require_tpu:
        devices = dev.require_tpu(cell.chips)
        peak = dev.peak_for(devices[0].device_kind)
    else:
        devices = jax.devices()[:cell.chips]
    _log(f"cell {workload}: config {cell.config_name}, traffic "
         f"{cell.traffic_name}, seed {seed}, {seconds} s, trace {int(trace)}, "
         f"device {dev.describe(devices)}, compile cache {cache}")
    workdir = os.path.join(root, "bench", ".work", workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds),
                  traced=bool(trace), workdir=workdir, variant=variant,
                  plant=plant or (lambda point, obj: obj), peak=peak or {})
    drv = spec.driver(cell, root)
    compiles = CompileCounter()
    st = None
    try:
        st = drv.setup(ctx)
        setup_s = time.perf_counter() - t_start
        trace_dir = os.path.join(workdir, "trace")
        compiles.armed = True
        t0 = time.perf_counter()
        if trace:
            with tr.Session(trace_dir):
                with ctx.probe.span("window"):
                    e2e = drv.window(ctx, st)
        else:
            with ctx.probe.span("window"):
                e2e = drv.window(ctx, st)
        window_s = time.perf_counter() - t0
        compiles.armed = False
        _log(f"window {window_s:.3f} s, set-up {setup_s:.3f} s, "
             f"compilations inside the window: {compiles.compiles}, "
             f"loads from the persistent cache: {compiles.cache_hits}; "
             f"requested at {compiles.where}")
        mem = dev.memory_peak_bytes(devices)
        if trace:
            ctx.trace = tr.reduce_events(tr.load_events(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        attempted, failed, checks = drv.check(ctx, st)
    finally:
        if st is not None:
            drv.close(ctx, st)
        shutil.rmtree(workdir, ignore_errors=True)
    metrics: Dict[str, Any] = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        for m in cell.per_layer:
            value = spec.reader(m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise KeyError(f"driver {cell.traffic['driver']} did not "
                               f"measure {m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]],
                                  "unit": units[m["name"]]}
    desc = dev.describe(devices)
    desc["memory_peak_bytes"] = mem
    if trace:
        desc["busy_s"] = ctx.trace.busy_s
        desc["window_s"] = ctx.trace.window_s
    for c in checks:
        _log(f"check {c.name}: {c.value!r} limit {'' if c.upper else 'at least '}"
             f"{c.limit!r} {'ok' if c.ok else 'FAILED'}")
    line: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": desc}
    if trace:
        line["breakdown"] = ctx.trace.breakdown()
    line["checks"] = {c.name: {"value": finite(c.value), "limit": c.limit}
                      for c in checks}
    return line
