"""Compile the serving path's device programs for one described TPU v5e chip.

No chip is attached: the TPU compiler compiles for a topology that is
described, not present, at qwen2.5-3b's published widths in bf16 with the
sizes ``chip_smoke.py`` serves at (B=8, gamma_max=8, 64-token blocks).  This
catches what the chip's compiler would refuse (layouts, tiling, a program
that does not fit the chip's memory) before any chip time is spent.

The TPU library may be loaded by one process at a time and is held until
that process exits, so the topology is described inside a module fixture
(never at import, in ``skipif`` or in ``parametrize``), every program is
compiled in this process, and all such compiles live in this one file.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import draft_config, get_config
from repro.core import make_controller
from repro.core.spec_decode import fused_session_tick
from repro.models import transformer as T
from repro.models.cache import build_cache_spec, build_paged_cache_spec

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

HBM_BYTES = 16e9          # one v5e chip
PROGRAMS = ("target_verify", "draft_decode", "target_paged_verify",
            "fused_tick")


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e 2x2 host, with the persistent
    compilation cache off (an executable compiled for a described chip is
    written to it but cannot be read back without one)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shapes(one_chip):
    """Abstract params, caches and cache specs on the described chip."""
    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    bf16 = jnp.bfloat16
    spec = chip_smoke.smoke_spec(chip_smoke.Traffic())
    B, L = spec.batch_size, spec.max_len
    paged = dict(block_size=spec.block_size, pool_tokens=spec.pool_tokens)
    out = {"B": B, "gamma": chip_smoke.GAMMA_MAX,
           "tok": lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,
                                                  sharding=one_chip),
           "on_chip": on_chip}
    for role, cfg in (("t", get_config(chip_smoke.ARCH)),
                      ("d", draft_config(chip_smoke.ARCH))):
        out[role + "cfg"] = cfg
        out[role + "params"] = on_chip(jax.eval_shape(
            lambda k: T.init_params(cfg, k, dtype=bf16),
            jax.random.PRNGKey(0)))
        out[role + "spec"] = build_cache_spec(cfg, L)
        out[role + "cache"] = on_chip(jax.eval_shape(
            lambda: T.init_cache(cfg, B, L, bf16)[0]))
        out[role + "pspec"] = build_paged_cache_spec(cfg, L, **paged)
        out[role + "pcache"] = on_chip(jax.eval_shape(
            lambda: T.init_paged_cache(cfg, B, L, dtype=bf16, **paged)[0]))
    return out


def _lower(name, s):
    B, g, tok = s["B"], s["gamma"], s["tok"]
    tcfg, dcfg = s["tcfg"], s["dcfg"]
    if name == "target_verify":
        fn = functools.partial(T.step, cfg=tcfg, spec=s["tspec"],
                               all_logits=True)
        return jax.jit(lambda p, t, c: fn(p, tokens=t, cache=c)).lower(
            s["tparams"], tok(B, g + 1), s["tcache"])
    if name == "draft_decode":
        fn = functools.partial(T.step, cfg=dcfg, spec=s["dspec"])
        return jax.jit(lambda p, t, c: fn(p, tokens=t, cache=c)).lower(
            s["dparams"], tok(B, 1), s["dcache"])
    if name == "target_paged_verify":
        fn = functools.partial(T.paged_step, cfg=tcfg, spec=s["tpspec"],
                               all_logits=True)
        return jax.jit(lambda p, t, c: fn(p, tokens=t, cache=c)).lower(
            s["tparams"], tok(B, g + 1), s["tpcache"])
    assert name == "fused_tick"
    arms = make_controller(chip_smoke.CONTROLLER, gamma_max=g).arms
    keys = s["on_chip"](jax.eval_shape(
        lambda: jax.random.split(jax.random.PRNGKey(0), B)))
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=keys.sharding)
    active = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=keys.sharding)
    tick = jax.jit(functools.partial(
        fused_session_tick.__wrapped__, cfg_d=dcfg, cfg_t=tcfg,
        dspec=s["dpspec"], tspec=s["tpspec"], arms=arms, gamma_max=g,
        n_prompt_tokens=2, paged=True))
    return tick.lower(
        s["dparams"], s["tparams"], dcaches=s["dpcache"],
        tcaches=s["tpcache"], in_tokens=tok(B, 2), last_tokens=tok(B, 1),
        arm_mat=tok(B, g), lam=scalar, drngs=keys, vrngs=keys, active=active,
        lengths=tok(B), dkeep=tok(B), tkeep=tok(B))


@pytest.mark.parametrize("name", PROGRAMS)
def test_compiles_for_one_v5e_chip(name, shapes):
    compiled = _lower(name, shapes).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, (name, used)
    # the weights are bf16 at published widths: the target alone is >6 GB
    if name != "draft_decode":
        assert mem.argument_size_in_bytes > 6e9, mem.argument_size_in_bytes
    if name == "fused_tick":
        # the arms' top-1/top-2 are reductions; a sort over the vocabulary
        # in the draft loop cost about 19 ms of every tick on a v5e
        assert "sort(" not in compiled.as_text()
