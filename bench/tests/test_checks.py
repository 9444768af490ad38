"""``correct`` at small sizes on the CPU: a sound run is correct; the
control (the reference one precision below the configuration, or the
program's own lower-precision path) is not; and neither is a run whose
timed path is broken underneath, once for each fault the cell can have
(a state left unchanged, half of the batch left out, an answer altered
where it is produced).  One chip: no exchange between chips to leave
out."""
import numpy as np
import pytest

import conftest
from harness.runner import run_cell

SEED = 2 ** 31 + 77


def run(root, cell, variant=None, plant=None, seconds=0.3):
    return run_cell(cell, seed=SEED, seconds=seconds, trace=False, root=root,
                    require_tpu=False, peak=conftest.CPU_PEAK,
                    variant=variant, plant=plant)


def planter(point, wrap):
    return lambda p, obj: wrap(obj) if p == point else obj


# -- fleet: the decide kernel's destinations --------------------------------

def dest_altered(fn):
    def f(batch, params):
        out = np.array(fn(batch, params))
        s = int(batch.s_i[0, 0])
        out[0, 0] = -1 if out[0, 0] >= 0 else (s + 1) % batch.n_sites[0]
        return out
    return f


def dest_unchanged(fn):
    return lambda batch, params: np.full(batch.sizes.shape, -1)


def dest_half(fn):
    def f(batch, params):
        out = np.array(fn(batch, params))
        for b, k in enumerate(batch.n_jobs):
            out[b, (k + 1) // 2:] = -1
        return out
    return f


def test_fleet_sound_run_is_correct(small_root, decide_backend):
    line = run(small_root, "small-fleet-paper")
    assert line["correct"], line["checks"]
    assert line["checks"]["rows_checked"]["value"] > 0


def test_fleet_control_is_not_correct(small_root, decide_backend):
    # bfloat16 flips a few of the ~6,000 destinations of most episodes,
    # not of every one: the window runs several
    line = run(small_root, "small-fleet-paper", "control", seconds=4.0)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", [dest_altered, dest_unchanged, dest_half])
def test_fleet_faults_are_caught(small_root, decide_backend, fault):
    line = run(small_root, "small-fleet-paper",
               plant=planter("decide_kernel", fault))
    assert not line["correct"], line["checks"]


# -- training: loss, first gradient and update against the reference --------

def step_unchanged(fn):
    def f(params, opt_state, batch):
        _, _, metrics = fn(params, opt_state, batch)
        return params, opt_state, metrics
    return f


def step_half_batch(fn):
    def f(params, opt_state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return fn(params, opt_state, half)
    return f


def test_train_sound_run_is_correct(small_root):
    line = run(small_root, "small-smollm2-train")
    assert line["correct"], line["checks"]


def test_train_control_is_not_correct(small_root):
    line = run(small_root, "small-smollm2-train", "control")
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", [step_unchanged, step_half_batch])
def test_train_faults_are_caught(small_root, fault):
    line = run(small_root, "small-smollm2-train",
               plant=planter("train_step", fault))
    assert not line["correct"], line["checks"]


# -- migration: the restored state and the first step after it ---------------

def restore_unchanged(fn):
    def f():
        fn.__self__.init_state()  # the fresh state, not the checkpoint
        return fn.__self__.step
    return f


def save_altered(fn):
    tr = fn.__self__
    real = tr.ckpt.save

    def save_one_off(step, state, mode=None):
        state = dict(state, params=dict(state["params"]))
        fn_ = state["params"]["final_norm"]
        state["params"]["final_norm"] = {"scale": fn_["scale"] + 1.0}
        return real(step, state, mode=mode)

    def f():
        tr.ckpt.save = save_one_off
        try:
            return fn()
        finally:
            tr.ckpt.save = real
    return f


def first_step_unchanged(fn):
    tr = fn.__self__

    def f(**kw):
        p, o = tr.params, tr.opt_state
        out = fn(**kw)
        tr.params, tr.opt_state = p, o
        return out
    return f


def test_migrate_sound_run_is_correct(small_root):
    line = run(small_root, "small-smollm2-migrate")
    assert line["correct"], line["checks"]


def test_migrate_control_is_not_correct(small_root):
    line = run(small_root, "small-smollm2-migrate", "control")
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("point,fault", [("restore", restore_unchanged),
                                         ("save", save_altered),
                                         ("first_step", first_step_unchanged)])
def test_migrate_faults_are_caught(small_root, point, fault):
    line = run(small_root, "small-smollm2-migrate", plant=planter(point, fault))
    assert not line["correct"], line["checks"]
