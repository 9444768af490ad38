"""The expert-share cell (``lfm2-moe-train``): its yardstick by hand,
its three readers on fixed inputs, its discovery by name, and small
runs on the CPU: a sound run is correct and reports its metrics; the
dropping control (capacity dispatch in place of the dropless layer) and
the bfloat16 control are not correct."""
import json
import os
from types import SimpleNamespace

import pytest

import conftest
from harness import moe_counts, program_spans, spec
from harness.runner import run_cell

SEED = 2 ** 31 + 41
#: the cut configuration at small widths: one dense conv layer, then an
#: attention and a conv layer with 4 of 8 experts held (experts 4-7)
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 4, "num_dense_layers": 1, "num_experts": 4,
        "router_experts": 8, "first_expert": 4, "num_experts_per_tok": 2,
        "vocab_size": 256,
        "layer_types": ["conv", "full_attention", "conv", "conv"]}
CFG = json.load(open(os.path.join(conftest.BENCH, "configs", "lfm2-8b-a1b.json")))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = conftest.make_small_root(str(tmp_path_factory.mktemp("moe")))
    b = os.path.join(root, "bench")
    json.dump({**CFG, **TINY, "name": "tiny-lfm2"},
              open(os.path.join(b, "configs", "tiny-lfm2.json"), "w"))
    t = json.load(open(os.path.join(b, "traffic", "train-steps-moe.json")))
    t.update(batch=4, seq=32, ref_rows=2, steps_per_call=2,
             limits={k: conftest.SMALL_LIMITS[k] for k in t["limits"]})
    json.dump(t, open(os.path.join(b, "traffic", "tiny-steps-moe.json"), "w"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny-lfm2", "source": "x",
                             "file": "bench/configs/tiny-lfm2.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-moe-train", "config": "tiny-lfm2",
                               "traffic": "tiny-steps-moe", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lfm2-moe-train" in m.get("workloads", ()):
            m["workloads"].append("tiny-moe-train")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"), indent=1)
    return root


def run(root, variant=None, trace=False):
    return run_cell("tiny-moe-train", seed=SEED, seconds=0.3, trace=trace,
                    root=root, require_tpu=False, peak=conftest.CPU_PEAK,
                    variant=variant)


# -- yardstick ---------------------------------------------------------------

def test_flops_by_hand():
    c = {**CFG, **TINY}
    d, q, kv, f = 64, 64, 32, 96
    per_token = (3 * (2 * (4 * d * d)) + 2 * (2 * d * q + 2 * d * kv)
                 + 2 * 2 * q * 33 / 2 + 2 * 3 * d * f
                 + 3 * 2 * d * 8 + 2 * d * 256)
    assert moe_counts.dense_fwd_flops_per_token(c, 32) == pytest.approx(per_token)
    assert moe_counts.expert_fwd_flops_per_row(c) == 2 * 3 * d * 32
    assert moe_counts.moe_train_flops(c, 32, 10, 7) == pytest.approx(
        3 * (10 * per_token + 7 * 2 * 3 * d * 32))


def test_expert_gmm_by_hand():
    c = {**CFG, **TINY}
    ops, nbytes = moe_counts.expert_gmm(c, rows=100, layer_calls=6)
    assert ops == 3 * 100 * 2 * 3 * 64 * 32
    assert nbytes == 3 * 4 * (100 * (3 * 64 + 3 * 32) + 6 * 3 * 4 * 64 * 32)


def test_lfm2_8b_a1b_cut():
    """The held share's parameters, as the configuration reckons them."""
    import jax
    import numpy as np

    ref = spec.load_module(os.path.join(conftest.BENCH, "configs",
                                        "lfm2_moe_ref.py"), "lfm2_ref_count")
    sds = jax.eval_shape(lambda k: ref.init_params(k, CFG), jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(sds)) == 568_647_936
    # 2,048 rows per held expert and layer on average at 8 x 2,048 tokens
    assert 8 * 2048 * CFG["num_experts_per_tok"] / CFG["router_experts"] == 2048


# -- readers on fixed inputs -------------------------------------------------

def _ctx(**info):
    from harness.trace import Event, Reduced

    ops = [Event("/device:TPU:0", "XLA Ops", "%fusion.7 = f32[8]", 0, 2e6),
           Event("/device:TPU:0", "XLA Ops", "%dot.3 = f32[8]", 0, 3e6),
           Event("/device:TPU:0", "XLA Ops", "%fusion.9 = f32[8]", 0, 5e6)]
    return SimpleNamespace(
        config={**CFG, **TINY}, traffic={"seq": 32}, peak=conftest.CPU_PEAK,
        trace=Reduced(window_s=1.0, busy_s=0.01, devices=1, ops=ops),
        info=info)


COUNTED = {"moe.rows_held": 600, "moe.rows_max": 300, "moe.steps_read": 2}


def test_readers_by_hand(monkeypatch):
    monkeypatch.setattr(program_spans, "registry", lambda: ([], dict(COUNTED)))
    ctx = _ctx(tokens=1000, wall_s=2.0, steps=4,
               scoped_ops={"moe.experts": ["fusion.7", "dot.3"]})
    c = ctx.config
    flops = moe_counts.moe_train_flops(c, 32, 1000, 300 * 4)
    assert spec.reader("moe_train_mfu").read(ctx) == pytest.approx(
        100 * flops / (2.0 * 1e12))
    ops, nbytes = moe_counts.expert_gmm(c, 300 * 4, 3 * 4)
    least = max(ops / 1e12, nbytes / 1e11)
    assert spec.reader("expert_gmm_roofline").read(ctx) == pytest.approx(
        100 * least / 5e-3)
    # 300 rows in the largest expert against 600 / 4 in the mean one
    assert spec.reader("expert_rows_imbalance").read(ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", ["moe_train_mfu", "expert_gmm_roofline",
                                    "expert_rows_imbalance"])
def test_nothing_counted_reads_none(monkeypatch, metric):
    monkeypatch.setattr(program_spans, "registry", lambda: ([], {}))
    ctx = _ctx(tokens=1000, wall_s=2.0, steps=4, scoped_ops={})
    assert spec.reader(metric).read(ctx) is None


# -- discovery and small runs ------------------------------------------------

def test_the_cell_resolves_with_its_driver_reference_and_metrics():
    cell = spec.resolve("lfm2-moe-train")
    assert cell.chips == 1 and cell.traffic["batch"] == 8
    assert spec.driver(cell).__name__ == "bench_driver_moe_train_steps"
    assert hasattr(spec.reference(cell), "batch_choices")
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "moe_train_mfu", "expert_gmm_roofline", "expert_rows_imbalance"}


def test_sound_traced_run_is_correct_and_reads_its_metrics(tiny_root):
    line = run(tiny_root, trace=True)
    assert line["correct"], line["checks"]
    got = line["metrics"]
    assert got["moe_train_mfu"]["value"] > 0
    assert got["expert_rows_imbalance"]["value"] >= 1.0


@pytest.mark.parametrize("variant", ["dropping", "control"])
def test_controls_are_not_correct(tiny_root, variant):
    line = run(tiny_root, variant=variant)
    assert not line["correct"], line["checks"]
