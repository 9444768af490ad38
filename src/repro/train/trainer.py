"""Preemption-aware trainer with checkpoint/restart and migration hooks.

This is the single-job execution engine that the paper's orchestrator
manages: it trains until (a) step budget, (b) a preemption signal (renewable
window closing / node failure), or (c) a migration order, checkpointing at
a bounded interval so at most `save_every` steps are ever lost — the
fault-tolerance contract for 1000+-node deployments.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro import telemetry
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import SyntheticLMDataset
from repro.models.model import Model
from repro.optim.adamw import init_opt_state
from repro.train.train_step import TrainStepConfig, make_train_step


@dataclass
class TrainerConfig:
    total_steps: int = 200
    save_every: int = 50
    ckpt_mode: str = "full"  # 'full' | 'int8' | 'delta-int8'
    log_every: int = 25
    seed: int = 0
    step_cfg: TrainStepConfig = field(default_factory=TrainStepConfig)
    # hand the step's input params and optimizer state to its outputs (a
    # state that fits only once on the device); the arrays passed in are
    # then deleted
    donate_state: bool = False


class Trainer:
    def __init__(
        self,
        model: Model,
        dataset: SyntheticLMDataset,
        ckpt: CheckpointManager,
        cfg: TrainerConfig,
        *,
        preempt_signal: Optional[Callable[[int], bool]] = None,
    ):
        self.model = model
        self.dataset = dataset
        self.ckpt = ckpt
        self.cfg = cfg
        self.preempt_signal = preempt_signal or (lambda step: False)
        self.train_step = jax.jit(make_train_step(model, cfg.step_cfg),
                                  donate_argnums=(0, 1) if cfg.donate_state else ())
        self.params = None
        self.opt_state = None
        self.step = 0
        self.history: List[Dict[str, float]] = []

    # -- state ---------------------------------------------------------------
    def init_state(self):
        key = jax.random.PRNGKey(self.cfg.seed)
        self.params = self.model.init(key)
        self.opt_state = init_opt_state(self.params)
        self.step = 0

    def state_tree(self):
        return {"params": self.params, "opt": self.opt_state,
                "step": np.int32(self.step)}

    def restore(self, shardings=None):
        """Resume from the newest checkpoint (crash restart or migration
        arrival). Returns the restored step."""
        if self.params is None:
            # a template of the state's structure; its values are replaced
            with telemetry.span("train.restore_template"):
                self.init_state()
        like = self.state_tree()
        tree, info = self.ckpt.restore(like, shardings=shardings)
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self.step = int(tree["step"])
        return self.step

    def save(self):
        self.ckpt.save(self.step, self.state_tree(), mode=self.cfg.ckpt_mode)

    # -- loop ----------------------------------------------------------------
    def run(self, max_steps: Optional[int] = None) -> Dict[str, Any]:
        """Train until budget or preemption. Returns a status dict."""
        if self.params is None:
            self.init_state()
        budget = min(
            self.cfg.total_steps,
            self.step + (max_steps if max_steps is not None else self.cfg.total_steps),
        )
        status = "done"
        while self.step < budget:
            if self.preempt_signal(self.step):
                self.save()
                status = "preempted"
                break
            with jax.profiler.StepTraceAnnotation("train", step_num=self.step):
                batch = self.dataset.batch(self.step)
                self.params, self.opt_state, metrics = self.train_step(
                    self.params, self.opt_state, batch
                )
            self.step += 1
            if self.step % self.cfg.log_every == 0 or self.step == budget:
                row = {k: float(v) for k, v in metrics.items()}
                row["step"] = self.step
                self.history.append(row)
                if "moe_rows_held" in row:
                    # a dropless expert layer's routed rows, of the steps read
                    telemetry.count("moe.rows_held", int(row["moe_rows_held"]))
                    telemetry.count("moe.rows_max", int(row["moe_rows_max"]))
                    telemetry.count("moe.steps_read")
            if self.step % self.cfg.save_every == 0:
                self.save()
        if status == "done" and self.step >= self.cfg.total_steps:
            self.save()
        return {
            "status": status,
            "step": self.step,
            "loss": self.history[-1]["loss"] if self.history else float("nan"),
            "ckpt_bytes": self.ckpt.latest_bytes,
        }
