"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
root of the checkout.  They run on the CPU in a copy of the benchmark
made under a temporary directory: the language-model cells at small
sizes, the fleet at its own (5 sites, 240 jobs)."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

#: stand-in peaks for CPU runs (the table refuses a device it lacks)
CPU_PEAK = {"flops_bf16": 1e12, "ops_int8": 2e12, "hbm_bytes_per_s": 1e11,
            "hbm_bytes": 1e10}

SMALL_LM = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 128,
            "vocab_size": 256}
SMALL_FLEET: dict = {}  # the paper's fleet runs on the CPU as it is
#: limits of the small cells, set between the CPU's largest readings of
#: sound runs over three seeds (loss 4.8e-7, gradient 2.1e-7, update
#: 4.8e-7, resume loss 4.8e-7) and the controls' smallest (bfloat16:
#: 1.7e-3, 6.2e-4, 8.1e-4; int8 checkpoint: 44 leaves differ, resume
#: loss 2.1e-5)
SMALL_LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-5, "update_gap": 1e-5,
                "resume_loss_gap": 1e-5}


def make_small_root(tmp) -> str:
    """A copy of the benchmark whose cells run at small sizes, added as
    new configuration and traffic files beside the real ones."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cdir = os.path.join(root, "bench", "configs")
    tdir = os.path.join(root, "bench", "traffic")
    small_cells = []
    for cfg in list(bench["configs"]):
        c = json.load(open(os.path.join(ROOT, cfg["file"])))
        c.update(SMALL_LM if "hidden_size" in c else SMALL_FLEET)
        name = "small-" + cfg["name"]
        c["name"] = name
        json.dump(c, open(os.path.join(cdir, name + ".json"), "w"))
        bench["configs"].append({**cfg, "name": name,
                                 "file": f"bench/configs/{name}.json"})
    for w in list(bench["workloads"]):
        t = json.load(open(os.path.join(tdir, w["traffic"] + ".json")))
        if "batch" in t:
            t.update(batch=4, seq=32, ref_rows=2, steps_per_call=2)
        if "limits" in t:
            t["limits"].update({k: v for k, v in SMALL_LIMITS.items()
                                if k in t["limits"]})
        json.dump(t, open(os.path.join(tdir, "small-" + w["traffic"] + ".json"), "w"))
        cell = {**w, "name": "small-" + w["name"],
                "config": "small-" + w["config"],
                "traffic": "small-" + w["traffic"]}
        bench["workloads"].append(cell)
        small_cells.append(cell["name"])
        for m in bench["end_to_end"] + bench["per_layer"]:
            if w["name"] in m.get("workloads", ()):
                m["workloads"].append(cell["name"])
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"), indent=1)
    return root


@pytest.fixture(scope="session")
def small_root(tmp_path_factory):
    return make_small_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture
def decide_backend():
    """The decide kernel's compiled XLA path on the CPU (the Pallas
    kernel needs the chip; its interpreter is too slow for a week)."""
    from repro.core import policy_kernels as pk

    pk.set_backend("jit")
    yield
    pk.set_backend(None)
