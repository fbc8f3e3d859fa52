"""Fixtures for the benchmark's own tests: a throwaway benchmark directory
that holds a tiny OPT-shaped configuration, its mixes and limits, beside
the real metric readers, peaks, architecture modules and references.  CPU
only: nothing here asks JAX for a device while modules are imported."""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

BENCH = ROOT / "bench"

TINY = {
    "name": "tiny-opt", "source": "test", "arch": "opt",
    "program_arch": "opt-125m",
    "reference": "opt_reference", "num_hidden_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "ffn_dim": 256,
    "vocab_size": 512, "max_position_embeddings": 256, "dtype": "float32",
    "wstream": "fp", "budget_bytes": 0, "page_size": 16,
    "control": "bf16",
    "init": {"embed_std_times_sqrt_d": 1.0, "norm_scale_std": 0.1,
             "norm_bias_std": 0.1, "bias_std": 0.1},
}
CLOSED = {
    "loop": "closed", "clients": 3, "slots": 3, "chunk_tokens": 32,
    "prompt": {"median": 24, "sigma": 0.6, "min": 8, "max": 64,
               "round_to": 8},
    "output": {"median": 64, "sigma": 0.6, "min": 32, "max": 128,
               "round_to": 1},
    "deck": 3, "decks": 40, "deck_seed": 5,
}
# On the CPU the tiny program reads max_logit_err about 2e-6 and its
# one-pass bfloat16 control above 1e-3; the limit sits between them.  As
# in the real limits, a run waits after the close until some requests
# have finished, so that a verdict never rests on how fast a loaded CPU
# served the window.
LIMITS = {"max_logit_gap": 1e-3, "max_logit_err": 1e-5,
          "sample_tokens": 400, "sample_requests": 8,
          "finished_at_least": 3, "wait_s": 120}


def make_bench_dir(root: Path, cells=None, configs=None):
    """A benchmark checkout under ``root``: BENCHMARK.json naming
    ``cells`` (name -> (config, traffic)), with the real metric readers,
    peaks, architecture modules and references copied beside the tiny
    files."""
    cells = cells or {"tiny.closed": ("tiny-opt", "tiny-closed")}
    configs = configs or {"tiny-opt": TINY}
    bench = root / "bench"
    for sub in ("metrics", "peaks"):
        shutil.copytree(BENCH / sub, bench / sub)
    (bench / "configs").mkdir(parents=True)
    for f in (BENCH / "configs").glob("*.py"):
        shutil.copy(f, bench / "configs")
    for name, conf in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(conf))
    (bench / "traffic").mkdir()
    (bench / "traffic" / "tiny-closed.json").write_text(json.dumps(CLOSED))
    (bench / "limits").mkdir()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": n, "source": "test",
                        "file": f"bench/configs/{n}.json", "reduced": [],
                        "why": "test"} for n in configs]
    spec["workloads"] = []
    for cell, (conf, mix) in cells.items():
        spec["workloads"].append({"name": cell, "config": conf,
                                  "traffic": mix, "chips": 1, "why": "t"})
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS))
    # the tiny cell stands in for the real one
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.closed" if w == "opt13b-fp.decode-batch"
                              else w for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def tiny_bench(tmp_path):
    from bench.harness import spec
    root = make_bench_dir(tmp_path)
    return spec.Bench(root=root, bench_dir=root / "bench")


@pytest.fixture
def cpu_only():
    if os.environ.get("JAX_PLATFORMS", "") not in ("cpu",):
        import jax
        if jax.default_backend() != "cpu":
            pytest.skip("these tests run the harness on the CPU backend")


@pytest.fixture(scope="module")
def jax_restored():
    """The harness sets JAX's compilation cache and matmul precision for
    the process; put them back once the module's runs are done."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_default_matmul_precision",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()
