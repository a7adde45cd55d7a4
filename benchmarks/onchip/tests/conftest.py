"""Tests of the benchmark's yardstick, on the CPU at tiny sizes.

Run them with ``python -m pytest benchmarks/onchip/tests``.  ``tiny_root``
builds a stand-alone benchmark root (a ``BENCHMARK.json`` and the files it
names) around a tiny Qwen2-shaped configuration, so that a whole run of the
harness fits a CPU.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks"))

import pytest  # noqa: E402

TINY_MODEL = {"architectures": ["Qwen2ForCausalLM"], "num_hidden_layers": 2,
              "hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "intermediate_size": 128, "vocab_size": 512,
              "hidden_act": "silu", "rope_theta": 10000.0,
              "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
              "attention_bias": True}
TINY_DRAFT = dict(TINY_MODEL, num_hidden_layers=2, hidden_size=32,
                  num_attention_heads=2, num_key_value_heads=1)


# The tiny model's readings (CPU, 8 seeds, 2-s windows, about 200 served
# tokens compared): the program's served_gap at most 0.021; the fp8 control
# at least 0.104.  So the tiny configuration's limit is 0.05.
TINY_LIMIT = 0.05


TINY_CHECK = {"served_gap_limit": TINY_LIMIT, "min_tokens": 200,
              "max_requests": 12}


def tiny_config() -> dict:
    model, draft = dict(TINY_MODEL), dict(TINY_DRAFT)
    return {"name": "tiny", "source": "tests", "reference": "qwen_dense",
            "model": model, "draft": draft,
            "serving": {"block_size": 16, "gamma_max": 4,
                        "controller": "tapout_seq_ucb1", "prefill_chunk": 16,
                        "kv_pool_bytes": 2.0e6, "reserve_headroom": 1.25},
            "weights": {"embed_std": 0.005, "norm_std": 0.1, "bias_std": 0.5},
            "reduced": []}


TINY_CLOSED = {"loop": "closed", "clients": 4,
               "prompt": {"kind": "lognormal", "median": 24, "sigma": 0.5,
                          "lo": 8, "hi": 48},
               "output": {"kind": "lognormal", "median": 24, "sigma": 0.5,
                          "lo": 12, "hi": 40},
               "pool_size": 256, "pool_seed": 0}
TINY_OPEN = dict(TINY_CLOSED, loop="open", lanes=4, preroll_s=0.5,
                 arrivals={"kind": "poisson", "rate": 20.0})
del TINY_OPEN["clients"]


def make_root(path: Path, config: dict, traffic: dict = None,
              check: dict = None) -> Path:
    """A benchmark root with cells ``tiny.closed`` and ``tiny.open``, and
    the repository's per-layer metrics (readers copied)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    d = path / "benchmarks" / "onchip"
    (d / "configs").mkdir(parents=True)
    (d / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", d / "metrics")
    (d / "configs" / "tiny.json").write_text(json.dumps(config))
    (d / "traffic" / "tiny_closed.json").write_text(json.dumps(traffic or TINY_CLOSED))
    (d / "traffic" / "tiny_open.json").write_text(json.dumps(TINY_OPEN))
    (d / "checks").mkdir()
    for cell in ("tiny.closed", "tiny.open"):
        (d / "checks" / f"{cell}.json").write_text(json.dumps(check or TINY_CHECK))
    cells = [{"name": "tiny.closed", "config": "tiny", "traffic": "tiny_closed",
              "chips": 1, "why": "test"},
             {"name": "tiny.open", "config": "tiny", "traffic": "tiny_open",
              "chips": 1, "why": "test"}]
    e2e = [dict(m, workloads=["tiny.closed"] if m["name"] == "output_tok_s"
                else ["tiny.open"]) if "workloads" in m else m
           for m in bench["end_to_end"]]
    per_layer = [dict(m, workloads=["tiny.open"] if "qwen2.5-3b.rag_open"
                      in m["workloads"] else ["tiny.closed"])
                 for m in bench["per_layer"]]
    (path / "BENCHMARK.json").write_text(json.dumps({
        "command": bench["command"], "paths": bench["paths"],
        "run_seconds": 3,
        "configs": [{"name": "tiny", "source": "tests",
                     "file": "benchmarks/onchip/configs/tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": cells, "end_to_end": e2e, "per_layer": per_layer}))
    return path


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path, tiny_config())
