"""End-to-end system behaviour: train -> checkpoint -> preempt -> migrate ->
resume on another 'site'; loss decreases; feasibility gates hold through the
whole stack."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core import feasibility as fz
from repro.core.migration import migrate_job
from repro.data.pipeline import SyntheticLMDataset
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.train import Trainer, TrainerConfig, TrainStepConfig


def make_trainer(tmp_path, site="siteA", steps=30, seed=0, ckpt_mode="full",
                 grad_compress=False):
    cfg = get_config("micro-lm").reduced()
    model = build_model(cfg)
    data = SyntheticLMDataset(cfg.vocab_size, 32, 4, seed=seed)
    ckpt = CheckpointManager(os.path.join(str(tmp_path), site), job="job0")
    return Trainer(
        model, data, ckpt,
        TrainerConfig(
            total_steps=steps, save_every=10, log_every=5, ckpt_mode=ckpt_mode,
            step_cfg=TrainStepConfig(
                opt=AdamWConfig(lr=3e-3), total_steps=steps, warmup_steps=3,
                grad_compress=grad_compress,
            ),
        ),
    )


def test_training_loss_decreases(tmp_path):
    tr = make_trainer(tmp_path, steps=40)
    status = tr.run()
    assert status["status"] == "done"
    losses = [h["loss"] for h in tr.history]
    assert losses[-1] < losses[0] - 0.3, losses


def test_preemption_checkpoints_and_restart(tmp_path):
    tr = make_trainer(tmp_path, steps=50)
    tr.preempt_signal = lambda step: step >= 17  # window closes at step 17
    status = tr.run()
    assert status["status"] == "preempted"
    assert status["step"] == 17
    # crash-restart: a fresh trainer restores and continues
    tr2 = make_trainer(tmp_path, steps=50)
    step = tr2.restore()
    assert step == 17
    status2 = tr2.run()
    assert status2["status"] == "done" and status2["step"] == 50


def test_restart_equals_uninterrupted(tmp_path):
    """Checkpoint/restart is bitwise-transparent: interrupted+resumed
    training equals the uninterrupted run (same data stream by step)."""
    tr_ref = make_trainer(tmp_path, site="ref", steps=20)
    tr_ref.run()
    tr_a = make_trainer(tmp_path, site="ab", steps=20)
    tr_a.preempt_signal = lambda step: step >= 10
    tr_a.run()
    tr_b = make_trainer(tmp_path, site="ab", steps=20)
    tr_b.restore()
    tr_b.run()
    for a, b in zip(jax.tree.leaves(tr_ref.params), jax.tree.leaves(tr_b.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_full_migration_cycle(tmp_path):
    """The paper's end-to-end story on real training state: train at site A,
    renewable window closes -> checkpoint -> feasibility-check -> WAN
    transfer -> restore at site B -> finish. Final state identical to an
    unmigrated run."""
    # reference: uninterrupted
    ref = make_trainer(tmp_path, site="ref", steps=24)
    ref.run()

    # site A: preempted at step 12
    a = make_trainer(tmp_path, site="A", steps=24)
    a.preempt_signal = lambda step: step >= 12
    sa = a.run()
    assert sa["status"] == "preempted"

    # orchestrator decision on the MEASURED checkpoint
    S = a.ckpt.latest_bytes
    v = fz.evaluate(S, 10e9, 2.5 * 3600)
    assert bool(v.feasible)

    dst_mgr, report = migrate_job(a.ckpt, os.path.join(str(tmp_path), "B"),
                                  bandwidth_bps=10e9, window_s=2.5 * 3600)
    assert report.feasible_in_window and report.workload_class == 0

    # site B: restore and finish
    b = make_trainer(tmp_path, site="B", steps=24)
    b.ckpt = dst_mgr
    assert b.restore() == 12
    sb = b.run()
    assert sb["status"] == "done" and sb["step"] == 24
    for x, y in zip(jax.tree.leaves(ref.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_int8_checkpoint_still_trains(tmp_path):
    """Compressed (int8) checkpoints lose precision but training continues
    and converges after restore — the paper's §VIII envelope expansion is
    safe."""
    a = make_trainer(tmp_path, site="A8", steps=40, ckpt_mode="int8")
    a.preempt_signal = lambda step: step >= 20
    a.run()
    b = make_trainer(tmp_path, site="A8", steps=40, ckpt_mode="int8")
    b.restore()
    status = b.run()
    assert status["status"] == "done"
    losses = [h["loss"] for h in b.history]
    assert losses[-1] < 5.0  # still learning after lossy restore


def test_grad_compress_trains(tmp_path):
    tr = make_trainer(tmp_path, site="gc", steps=30, grad_compress=True)
    status = tr.run()
    losses = [h["loss"] for h in tr.history]
    assert losses[-1] < losses[0] - 0.2


def test_serve_decode_runs():
    from repro.launch.serve import greedy_decode

    cfg = get_config("micro-lm").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, cfg.vocab_size)
    seqs = greedy_decode(model, params, prompt, max_new=6, cache_len=10)
    assert seqs.shape == (2, 10)


def test_serve_green_routing_uses_shared_state():
    """Serve-layer routing builds the same ClusterState snapshot as the
    simulator and fills renewable capacity before spilling to grid sites."""
    from repro.launch.serve import build_serving_state, green_route

    state = build_serving_state("solar-heavy", at_hour=13.0)
    assert len(state.sites) == 5
    routes = green_route(state, 16)
    assert len(routes) == 16
    green = {s.sid for s in state.sites if s.renewable_active}
    free_green_slots = sum(s.slots - s.busy for s in state.sites
                           if s.renewable_active)
    head = routes[:min(16, free_green_slots)]
    assert green, "solar-heavy at 13:00 must have at least one green site"
    assert all(sid in green for sid in head)


def test_orchestration_plan_preview():
    """The dry-run planner produces typed actions from a scenario snapshot
    without running the simulator."""
    from repro.core.actions import Action
    from repro.launch.dryrun import plan_orchestration

    state, actions = plan_orchestration("paper-table6", "feasibility-aware",
                                        at_hour=36.0)
    assert len(state.sites) == 5
    assert len(state.jobs) > 0
    assert all(isinstance(a, Action) for a in actions)


def _train_losses(capsys, argv):
    """Run the training launcher; {step: loss} from the history it prints."""
    import json

    from repro.launch import train

    assert train.main(argv) == 0
    out = capsys.readouterr().out
    return out, {r["step"]: r["loss"] for r in map(
        json.loads, (ln for ln in out.splitlines()
                     if ln.strip().startswith("{")))}


def test_train_launcher_split_session_migrates_and_resumes_exactly(
        tmp_path, capsys):
    """launch/train.py --max-steps checkpoints mid-schedule; after
    migrate_job to another site directory, --resume there continues to
    --steps with exactly the losses of an uninterrupted run."""
    common = ["--arch", "micro-lm", "--smoke", "--steps", "4", "--batch",
              "2", "--seq", "16", "--log-every", "1", "--save-every", "4"]
    _, ref = _train_losses(
        capsys, common + ["--ckpt-dir", str(tmp_path / "ref")])
    _, first = _train_losses(capsys, common + [
        "--ckpt-dir", str(tmp_path / "A"), "--max-steps", "2"])
    src = CheckpointManager(str(tmp_path / "A"), job="micro-lm-smoke")
    assert src.latest.step == 2
    migrate_job(src, str(tmp_path / "B"))
    out, second = _train_losses(capsys, common + [
        "--ckpt-dir", str(tmp_path / "B"), "--resume"])
    assert "resumed from step 2" in out
    assert sorted(ref) == [1, 2, 3, 4]
    assert first == {s: ref[s] for s in (1, 2)}
    assert second == {s: ref[s] for s in (3, 4)}


@pytest.mark.parametrize("where", ["cpu", "alone"])
def test_chip_smoke_refuses_without_chip_or_repo(tmp_path, where):
    """chip_smoke.py runs only on a TPU and only from a checkout: on the
    CPU, or copied out of the repository, it exits non-zero and prints
    no result line."""
    import shutil
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=tmp_path,
        timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert ("no TPU found" if where == "cpu" else "no src/repro") in out.stderr
