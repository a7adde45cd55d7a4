"""Sharding rules + a REAL multi-device integration test (subprocess with 8
forced host devices running an actual sharded train step numerically)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.launch.shardings import cache_spec, param_spec
from repro.models.sharding import resolve_spec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_spec_rules():
    assert param_spec("embed", (1000, 64)) == ("model", ("pod", "data"))
    assert param_spec("layers/prefix/0/mixer/wq", (64, 128)) == \
        (("pod", "data"), "model")
    assert param_spec("layers/stack/0/ffn/experts/w_in", (4, 8, 64, 128))[0] is None
    assert param_spec("layers/prefix/0/norm1", (64,)) == (None,)
    assert param_spec("layers/tail/1/ffn/w_out", (256, 64)) == \
        ("model", ("pod", "data"))


def test_cache_spec_rules():
    # GQA with 16-divisible heads: shard heads
    assert cache_spec("layers/prefix/0/k", (8, 1024, 16, 128))[2] == "model"
    # MQA: shard sequence instead
    assert cache_spec("layers/prefix/0/k", (8, 1024, 1, 128))[1] == "model"
    assert cache_spec("layers/prefix/0/pos", (1024,)) == (None,)
    assert cache_spec("layers/prefix/0/ssm", (8, 16, 32, 64))[1] == "model"


def test_resolve_spec_drops_indivisible():
    import jax
    mesh = jax.make_mesh((1,), ("model",))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 2}

    spec = resolve_spec(FakeMesh(), [("pod", "data"), "model"], (8, 6))
    # pod missing -> dropped; data divides 8; model=2 divides 6
    assert spec[0] == "data" and spec[1] == "model"
    spec2 = resolve_spec(FakeMesh(), ["data", "model"], (6, 5))
    assert spec2[0] is None and spec2[1] is None  # 6%4, 5%2


def test_host_mesh_axes_are_auto():
    """Meshes the launch layer builds leave sharding to GSPMD (Auto axes):
    under JAX's Explicit default the embedding gather raises."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=1, model=1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


_MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.registry import smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.shardings import batch_shardings, params_shardings
    from repro.models import transformer as T
    from repro.models.sharding import use_mesh
    from repro.training.optimizer import OptConfig, init_opt_state
    from repro.training.train_loop import make_train_step

    assert len(jax.devices()) == 8
    mesh = make_host_mesh(data=2, model=4)
    cfg = smoke_config("qwen3-moe-235b-a22b").replace(vocab_size=512)
    with use_mesh(mesh):
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 512)}
        batch["labels"] = jnp.roll(batch["tokens"], -1, 1)
        psh = params_shardings(mesh, params)
        params = jax.device_put(params, psh)
        step = make_train_step(cfg, OptConfig(lr=1e-3, total_steps=5),
                               remat=True, donate=False)
        fn = jax.jit(step)
        losses = []
        for _ in range(3):
            params, opt, m = fn(params, opt, batch)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0], losses
        # params really are distributed
        leaf = jax.tree.leaves(params)[3]
        assert len(leaf.sharding.device_set) >= 1
        print("MULTIDEV_OK", losses)
""")


@pytest.mark.slow
def test_multidevice_sharded_train_step():
    """8 forced host devices, (2,4) mesh, sharded MoE train steps converge."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _MULTIDEV], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "MULTIDEV_OK" in r.stdout, r.stdout + "\n" + r.stderr
