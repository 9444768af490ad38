"""Checkpoint manager: periodic/async saves, retention, restore with
resharding onto a (possibly different) mesh — the migration engine's
storage layer and the source of truth for the feasibility model's S_j.

Layout: <root>/<job>/step_<N>/ checkpoint.bin  (manifest embedded).
"""
from __future__ import annotations

import os
import shutil
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax

from repro import telemetry
from repro.checkpoint import serializer as ser


@dataclass
class CheckpointInfo:
    job: str
    step: int
    path: str
    nbytes: int
    mode: str


class CheckpointManager:
    def __init__(
        self,
        root: str,
        job: str = "job0",
        *,
        mode: str = "full",
        keep: int = 3,
        async_save: bool = False,
    ):
        self.root = root
        self.job = job
        self.mode = mode
        self.keep = keep
        self.async_save = async_save
        self._history: List[CheckpointInfo] = []
        self._base_cache: Optional[Any] = None  # last full state (delta base)
        self._pending: Optional[threading.Thread] = None
        os.makedirs(self._job_dir(), exist_ok=True)
        self._scan_existing()

    # -- paths ---------------------------------------------------------------
    def _job_dir(self) -> str:
        return os.path.join(self.root, self.job)

    def _step_dir(self, step: int) -> str:
        return os.path.join(self._job_dir(), f"step_{step:08d}")

    def _scan_existing(self):
        for name in sorted(os.listdir(self._job_dir())):
            if name.startswith("step_"):
                p = os.path.join(self._job_dir(), name, "checkpoint.bin")
                if os.path.exists(p):
                    step = int(name.split("_")[1])
                    self._history.append(
                        CheckpointInfo(self.job, step, p, os.path.getsize(p), "?")
                    )

    # -- API ------------------------------------------------------------------
    @property
    def latest(self) -> Optional[CheckpointInfo]:
        return self._history[-1] if self._history else None

    @property
    def latest_bytes(self) -> int:
        """S_j for the feasibility model — measured, not estimated."""
        self.wait()
        return self.latest.nbytes if self.latest else 0

    def wait(self):
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    def save(self, step: int, state, *, mode: Optional[str] = None) -> CheckpointInfo:
        """Serialize + persist `state` (any pytree: params or full train
        state). delta-int8 uses the previous save as base."""
        mode = mode or self.mode
        with telemetry.span("ckpt.save"):
            with telemetry.span("ckpt.save.gather"):  # device -> host
                host_state = jax.tree.map(ser.host_array, state)
            base = self._base_cache if mode == "delta-int8" else None
            if mode == "delta-int8" and base is None:
                mode = "int8"  # first checkpoint has no base

            def _write() -> CheckpointInfo:
                with telemetry.span("ckpt.save.encode"):
                    image = ser.encode(host_state, mode=mode, base=base)
                with telemetry.span("ckpt.save.write"):
                    d = self._step_dir(step)
                    os.makedirs(d, exist_ok=True)
                    path = os.path.join(d, "checkpoint.bin")
                    with open(path, "wb") as f:
                        nbytes = ser.write(f, image)
                return CheckpointInfo(self.job, step, path, nbytes, mode)

            self.wait()
            if self.async_save:
                # host_state is already gathered: the device-side training
                # loop can proceed while encode and IO happen off-thread,
                # streaming the raw leaves from host_state itself.
                info = CheckpointInfo(self.job, step, "", 0, mode)

                def run():
                    done = _write()
                    info.path, info.nbytes = done.path, done.nbytes

                self._pending = threading.Thread(target=run, daemon=True)
                self._pending.start()
            else:
                info = _write()
            self._base_cache = host_state
            self._history.append(info)
            self._gc()
            return info

    def restore(
        self,
        like,
        *,
        step: Optional[int] = None,
        shardings=None,
        base: Optional[Any] = None,
    ):
        """Load a checkpoint into the structure of `like`. If `shardings`
        (pytree of NamedSharding) is given, leaves are placed onto the new
        mesh — this is how a migrated job resumes on a *different* slice
        (elastic restore)."""
        self.wait()
        infos = [i for i in self._history if step is None or i.step == step]
        if not infos:
            raise FileNotFoundError(f"no checkpoint for {self.job} step={step}")
        info = infos[-1]
        with telemetry.span("ckpt.restore"):
            with telemetry.span("ckpt.restore.read"):
                with open(info.path, "rb") as f:
                    manifest, parts = ser.read(f)
            if manifest["mode"] == "delta-int8" and base is None:
                base = self._base_cache
            with telemetry.span("ckpt.restore.decode"):
                tree = ser.decode(manifest, parts, like, base=base)
                if shardings is not None:
                    tree = jax.tree.map(
                        lambda x, s: jax.device_put(x, s), tree, shardings
                    )
        return tree, info

    def _gc(self):
        while len(self._history) > self.keep:
            old = self._history.pop(0)
            shutil.rmtree(os.path.dirname(old.path), ignore_errors=True)

    # -- migration support -----------------------------------------------------
    def export_bytes(self, step: Optional[int] = None) -> bytes:
        self.wait()
        infos = [i for i in self._history if step is None or i.step == step]
        with open(infos[-1].path, "rb") as f:
            return f.read()

    @staticmethod
    def import_bytes(root: str, job: str, step: int, raw: bytes) -> "CheckpointManager":
        mgr = CheckpointManager(root, job)
        d = mgr._step_dir(step)
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "checkpoint.bin")
        with open(path, "wb") as f:
            f.write(raw)
        mgr._history.append(CheckpointInfo(job, step, path, len(raw), "?"))
        return mgr
