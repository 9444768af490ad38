"""A configuration, a traffic mix and a per-layer metric added as new
files, with entries in BENCHMARK.json, are found by name and run, and no
file the benchmark already had is edited."""
import hashlib
import json
import os

import conftest
from harness import spec
from harness.runner import run_cell


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            out[p] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    root = conftest.make_small_root(str(tmp_path))
    before = _digests(root)
    b = os.path.join(root, "bench")
    cfg = json.load(open(os.path.join(b, "configs", "small-smollm2-135m.json")))
    cfg.update(name="tiny-lm", intermediate_size=96)
    json.dump(cfg, open(os.path.join(b, "configs", "tiny-lm.json"), "w"))
    traffic = json.load(open(os.path.join(b, "traffic", "small-train-steps.json")))
    traffic["batch"] = 2
    json.dump(traffic, open(os.path.join(b, "traffic", "tiny-steps.json"), "w"))
    with open(os.path.join(b, "metrics", "tokens_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.info['tokens'])\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny-lm", "source": "x",
                             "file": "bench/configs/tiny-lm.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-train", "config": "tiny-lm",
                               "traffic": "tiny-steps", "chips": 1,
                               "why": "x"})
    bench["end_to_end"][[m["name"] for m in bench["end_to_end"]].index(
        "train_tokens_per_s")]["workloads"].append("tiny-train")
    bench["per_layer"].append({"name": "tokens_seen", "unit": "tokens",
                               "better": "higher", "source": "program_counter",
                               "layer": "train step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["tiny-train"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = spec.resolve("tiny-train", root)
    assert cell.config["intermediate_size"] == 96 and cell.traffic["batch"] == 2
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    assert "tokens_seen" in [m["name"] for m in cell.per_layer]
    line = run_cell("tiny-train", seed=5, seconds=0.2, trace=True, root=root,
                    require_tpu=False, peak=conftest.CPU_PEAK)
    assert line["correct"]
    assert line["metrics"]["tokens_seen"]["value"] > 0
    after = _digests(root)
    assert all(after[p] == h for p, h in before.items())
