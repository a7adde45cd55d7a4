"""``chip_smoke.py`` off the chip: its serving-and-checking function on a
small bf16 pair, its four-chip phase on four virtual CPU devices, its
refusal to run without a TPU, and the compile-cache helper the entry points
call."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.registry import smoke_config
from repro.launch import compile_cache
from repro.launch.mesh import forced_host_env

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _smoke_pair():
    tcfg = smoke_config(chip_smoke.ARCH)
    dcfg = tcfg.replace(name=tcfg.name + "-draft", num_layers=1)
    return (chip_smoke.init_bundle(dcfg, chip_smoke.DRAFT_SEED),
            chip_smoke.init_bundle(tcfg, chip_smoke.TARGET_SEED))


def test_serve_and_check_bf16_smoke_pair():
    draft, target = _smoke_pair()
    traffic = chip_smoke.Traffic(n=3, lo=20, hi=40, max_new=6)
    rep = chip_smoke.serve_and_check(
        draft, target, traffic, batch_size=2,
        warmup=chip_smoke.Traffic(n=2, lo=20, hi=30, max_new=2, seed=3))
    assert rep["backend"] == "paged" and rep["fused"]
    assert rep["new_tokens"] >= traffic.n * traffic.max_new
    assert sum(rep["arm_pulls"].values()) > 0
    assert rep["warmup_compiles"] > 0 and rep["drain_compiles"] == 0
    gap = rep["logit_gap"]
    assert gap["rms_rel"] <= chip_smoke.LOGIT_RMS_REL
    assert gap["max_rel"] <= chip_smoke.LOGIT_MAX_REL
    assert all(f["deficit"] <= chip_smoke.GREEDY_DEFICIT_REL
               for f in rep["greedy_flips"])


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert f"platform {jax.devices()[0].platform!r}" in err
    assert '"ok"' not in out


_FOUR = """
import sys
sys.path.insert(0, {root!r})
import jax
assert len(jax.devices()) == 4, jax.devices()
import chip_smoke as cs
from repro.configs.registry import smoke_config
t = smoke_config(cs.ARCH)
for line in cs.four_chip_phase(t, t.replace(name="d", num_layers=1),
                               cs.Traffic(n=4, lo=20, hi=40, max_new=10)):
    print(line)
print("FOUR_CHIP_PHASE_OK")
"""


def test_four_chip_phase_on_virtual_devices():
    """The ``--chips 4`` phase end to end on four forced CPU devices:
    data=4 tokens identical to one device, data=2 x model=2 logits within
    the tensor-parallel bounds, weights on every device."""
    env = forced_host_env(4)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", _FOUR.format(root=ROOT)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert "FOUR_CHIP_PHASE_OK" in r.stdout, r.stdout + "\n" + r.stderr
    assert "tokens_identical=True" in r.stdout


@pytest.mark.parametrize("env_dir", [None, "/srv/shared/jax_cache"])
def test_use_compile_cache(monkeypatch, env_dir):
    """The environment's directory wins and nothing is set; otherwise one
    fixed directory inside the checkout."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.CACHE_ENV, env_dir)
    got = compile_cache.use_compile_cache()
    if env_dir is not None:
        assert got == env_dir and updates == []
    else:
        want = str(Path(ROOT).resolve() / "artifacts" / "jax_cache")
        assert got == want
        assert updates == [("jax_compilation_cache_dir", want)]
