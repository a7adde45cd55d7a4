"""The run as a whole: the refusals, the lookup of every file by name, and
the check that decides ``correct`` against a timed path broken on purpose."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, ROOT, TINY_CLOSED, make_root, tiny_config
from onchip import harness

RUN = BENCH / "run.py"


def _run_cmd(cwd, *args, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(cwd / "benchmarks/onchip/run.py"),
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_run_refuses_the_cpu():
    p = _run_cmd(ROOT, "--workload", "qwen2.5-3b.rag_open", "--seed",
                 "1", "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert "'cpu'" in p.stderr and "no CPU fallback" in p.stderr
    assert p.stdout.strip() == ""


def test_run_needs_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(BENCH, tmp_path / "benchmarks/onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run_cmd(tmp_path, "--workload", "qwen2.5-3b.rag_open",
                 "--seed", "1", "--seconds", "1", "--trace", "0",
                 env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", json.loads((ROOT / "BENCHMARK.json").read_text())
                         ["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves_its_files(cell):
    c = harness.load_cell(cell["name"])
    assert c.config["name"] == cell["config"]
    assert c.traffic["loop"] in ("closed", "open")
    assert 0 < c.check["served_gap_limit"] < 1
    for m in c.per_layer:
        assert callable(c.reader(m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    # no per-layer metric in a cell that lacks the end-to-end metric it moves
    assert {m["moves"] for m in c.per_layer} <= names
    lay = harness.Layout.of(c.config, c.traffic,
                            *(harness.counts.Dims.of(harness._reference_module(
                                c.config).Shape.from_config(c.config[k]))
                              for k in ("model", "draft")))
    assert lay.lanes >= 1 and lay.pool_tokens >= lay.slot_tokens


def test_new_traffic_file_is_found_by_name(tmp_path):
    root = make_root(tmp_path, tiny_config())
    spec = dict(TINY_CLOSED, clients=3)
    (root / "benchmarks/onchip/traffic/brand_new.json").write_text(json.dumps(spec))
    (root / "benchmarks/onchip/checks/tiny.brand_new.json").write_text(
        (root / "benchmarks/onchip/checks/tiny.closed.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.brand_new", "config": "tiny",
                               "traffic": "brand_new", "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny.brand_new", root)
    assert cell.traffic == spec
    out = harness.run_cell(cell, 11, 1.0, False, t_process=time.perf_counter(),
                           log=lambda m: None)
    assert out.detail["layout"]["lanes"] == 3
    assert out.line["correct"] and out.line["metrics"]["setup_s"]["value"] > 0


# ------------------------------------------------------------ the check

def _tiny_run(root, cell, seed, on_server=None, trace=False, controls=(),
              window=1.5):
    return harness.run_cell(harness.load_cell(cell, root), seed, window, trace,
                            t_process=time.perf_counter(), on_server=on_server,
                            controls=controls, log=lambda m: None)


def test_sound_run_is_correct_and_reports_its_metrics(tiny_root):
    out = _tiny_run(tiny_root, "tiny.open", 2 ** 33 + 1)
    line = out.line
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_mean_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["checks"]["served_gap"]["value"] <= line["checks"]["served_gap"]["limit"]


def _faulty_tick(kind):
    """Break the fused serving tick where its outcome is produced."""
    def install(server):
        eng = server.engine
        tick = eng._fused_tick
        vocab = eng.target.cfg.vocab_size

        def broken(dparams, tparams, dcaches, tcaches, *rest):
            ft = tick(dparams, tparams, dcaches, tcaches, *rest)
            if kind == "token":
                return ft._replace(out_tokens=(ft.out_tokens + 1) % vocab)
            if kind == "state":
                # the KV pools come back as they went in
                return ft._replace(
                    dcache={**ft.dcache, "layers": dcaches["layers"]},
                    tcache={**ft.tcache, "layers": tcaches["layers"]})
            # half of the lanes left out: they get the other half's outcome
            h = ft.out_tokens.shape[0] // 2
            idx = np.concatenate([np.arange(h), np.arange(h)])[:ft.out_tokens.shape[0]]
            return ft._replace(out_tokens=ft.out_tokens[idx],
                               n_accepted=ft.n_accepted[idx],
                               n_drafted=ft.n_drafted[idx])
        eng._fused_tick = broken
    return install


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_control_is_not_correct(tiny_root, seed):
    """The control: the reference with its weights in fp8 in the program's
    place.  At each served position, the token fp8 puts first lies below
    the reference's best by more than the limit; the program's do not."""
    out = _tiny_run(tiny_root, "tiny.closed", seed, controls=("fp8",), window=2.0)
    gaps, limit = out.detail["check"]["gaps"], out.line["checks"]["served_gap"]["limit"]
    assert gaps["program"] <= limit < gaps["fp8"], gaps


@pytest.mark.parametrize("fault", ["token", "state", "half_batch"])
def test_broken_timed_path_is_not_correct(tiny_root, fault):
    out = _tiny_run(tiny_root, "tiny.closed", 5, on_server=_faulty_tick(fault))
    assert out.line["correct"] is False, out.line["checks"]
