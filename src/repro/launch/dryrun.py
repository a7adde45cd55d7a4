"""Multi-pod dry-run: prove every (arch x input-shape x mesh) combination
lowers, compiles, fits, and report its roofline terms.

MUST be run as a module/script, never imported by tests or library code:
importing this module sets ``XLA_FLAGS`` to force 512 host devices, which
only takes effect if jax has not initialized yet — and would silently
leave a test process at 1 device (or, worse, poison a later jax init in
the same process) if imported casually.  The env assignment sits below
this docstring but ABOVE the first ``import jax``, which is what makes
the trick work while keeping this text the module's real ``__doc__``
(docs/sharding.md#dryrun).

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape decode_32k [--multi-pod]
  python -m repro.launch.dryrun --all            # every pair, both meshes
"""
import os

# Force 512 virtual host devices BEFORE jax (imported below) initializes,
# and pin the CPU: on a machine with a TPU, JAX would otherwise take the
# chip and fail to build the 256-device mesh from it.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis.roofline import build_roofline
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (batch_shardings, cache_shardings,
                                    params_shardings, replicated)
from repro.launch.specs import SHAPES, abstract_cache, abstract_params, input_specs
from repro.models import transformer as T
from repro.models.sharding import use_mesh
from repro.training.optimizer import OptConfig, init_opt_state
from repro.training.train_loop import make_train_step

__all__ = ["lower_pair", "main"]

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def lower_pair(arch: str, shape: str, *, multi_pod: bool = False,
               compile_: bool = True, verbose: bool = True,
               unroll: bool = False, cfg_overrides: dict = None,
               train_microbatches: int = 1, donate_cache: bool = False,
               cache_int8: bool = False, argmax_out: bool = False,
               serve_resident: bool = False) -> dict:
    cfg = get_config(arch)
    if unroll:   # accurate cost_analysis for the roofline (scan counts once)
        cfg = cfg.replace(scan_layers=False)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    sh = SHAPES[shape]
    kind, B, S = sh["kind"], sh["batch"], sh["seq_len"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = mesh.devices.size
    t0 = time.perf_counter()

    with use_mesh(mesh):
        ins = input_specs(cfg, shape)
        if kind == "train":
            params = abstract_params(cfg, jnp.float32)
            opt = jax.eval_shape(init_opt_state, params)
            psh = params_shardings(mesh, params)
            # opt state mirrors params: reuse param shardings for mu/nu
            from repro.training.optimizer import OptState
            osh = OptState(replicated(mesh),
                           params_shardings(mesh, opt.mu),
                           params_shardings(mesh, opt.nu))
            bsh = batch_shardings(mesh, ins)
            train_fn = make_train_step(cfg, OptConfig(),
                                       compute_dtype=jnp.bfloat16,
                                       microbatches=train_microbatches,
                                       donate=False)   # raw fn
            fn = jax.jit(train_fn, in_shardings=(psh, osh, bsh))
            lowered = fn.lower(params, opt, ins)
        else:
            params = abstract_params(cfg, jnp.bfloat16)
            psh = params_shardings(mesh, params,
                                   mode="serve" if serve_resident else "train")
            if kind == "prefill":
                def prefill_fn(p, batch):
                    cache, spec = T.init_cache(cfg, B, S + 8, jnp.bfloat16)
                    logits, cache = T.step(p, cfg, batch["tokens"], cache,
                                           spec, **{k: v for k, v in batch.items()
                                                    if k not in ("tokens",)})
                    return logits, cache
                bsh = batch_shardings(mesh, ins)
                fn = jax.jit(prefill_fn, in_shardings=(psh, bsh))
                lowered = fn.lower(params, ins)
            else:  # decode: one token against a seq_len cache
                cache_dtype = jnp.int8 if cache_int8 else jnp.bfloat16
                cache, spec = abstract_cache(cfg, B, S, cache_dtype)
                csh = cache_shardings(mesh, cache)
                tsh = batch_shardings(mesh, {"tokens": ins["tokens"]})["tokens"]

                def decode_fn(p, tok, c):
                    logits, c = T.step(p, cfg, tok, c, spec)
                    if argmax_out:
                        # serving returns the sampled token, not the logits:
                        # distributed argmax over the vocab-sharded logits
                        # avoids the (B, V) all-gather entirely
                        return jnp.argmax(logits, axis=-1).astype(jnp.int32), c
                    return logits, c
                # donate the cache: in-place slot update instead of a full
                # copy-on-write of the KV buffers (§Perf decode iteration)
                fn = jax.jit(decode_fn, in_shardings=(psh, tsh, csh),
                             donate_argnums=(2,) if donate_cache else ())
                lowered = fn.lower(params, ins["tokens"], cache)

        t_lower = time.perf_counter() - t0
        result = {"arch": arch, "shape": shape, "mesh": mesh_name,
                  "chips": chips, "kind": kind, "lower_s": t_lower,
                  "status": "lowered"}
        if compile_:
            compiled = lowered.compile()
            t_comp = time.perf_counter() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            rl = build_roofline(arch, shape, mesh_name, chips, cost, hlo,
                                cfg, kind, B, S)
            result.update({
                "status": "compiled", "compile_s": t_comp,
                "memory": _mem_dict(mem), "roofline": rl.to_dict(),
            })
            if verbose:
                print(f"[{arch} x {shape} x {mesh_name}] COMPILED "
                      f"lower={t_lower:.1f}s compile={t_comp:.1f}s")
                print("  memory_analysis:", result["memory"])
                print("  roofline:", json.dumps(rl.to_dict(), indent=2))
    return result


def _mem_dict(mem) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll scan-over-layers for exact cost analysis")
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON result here")
    args = ap.parse_args()

    os.makedirs(args.out and os.path.dirname(args.out) or ARTIFACT_DIR,
                exist_ok=True)
    results = []
    if args.all:
        pairs = [(a, s, mp) for a in ARCH_IDS for s in SHAPES
                 for mp in (False, True)]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape, args.multi_pod)]
    rc = 0
    for arch, shape, mp in pairs:
        try:
            r = lower_pair(arch, shape, multi_pod=mp,
                           compile_=not args.lower_only, unroll=args.unroll)
        except Exception as e:
            traceback.print_exc()
            r = {"arch": arch, "shape": shape,
                 "mesh": "pod2x16x16" if mp else "pod16x16",
                 "status": "failed", "error": f"{type(e).__name__}: {e}"}
            rc = 1
        results.append(r)
    out_path = args.out or os.path.join(
        ARTIFACT_DIR, f"{pairs[0][0]}_{pairs[0][1]}_"
        f"{'multi' if pairs[0][2] else 'single'}.json")
    with open(out_path, "w") as f:
        json.dump(results if args.all else results[0], f, indent=2)
    print(f"wrote {out_path}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
