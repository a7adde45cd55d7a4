"""Hillclimb probe: lower one (arch x shape) with config overrides and print
the roofline terms + memory — the measurement half of each §Perf iteration.

    PYTHONPATH=src python scripts/perf_probe.py --arch gemma-2b \
        --shape decode_32k --set long_context_window=4096 [--unroll] [--multi-pod]
"""
import argparse
import ast
import json
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--unroll", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override k=v (python literal)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--donate-cache", action="store_true")
    ap.add_argument("--cache-int8", action="store_true")
    ap.add_argument("--argmax-out", action="store_true")
    ap.add_argument("--serve-resident", action="store_true")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    from repro.launch.dryrun import lower_pair
    r = lower_pair(args.arch, args.shape, multi_pod=args.multi_pod,
                   unroll=args.unroll, cfg_overrides=overrides,
                   train_microbatches=args.microbatches,
                   donate_cache=args.donate_cache,
                   cache_int8=args.cache_int8, argmax_out=args.argmax_out,
                   serve_resident=args.serve_resident, verbose=False)
    rl = r.get("roofline", {})
    mem = r.get("memory", {})
    print(json.dumps({
        "overrides": overrides,
        "status": r["status"],
        "t_compute_ms": rl.get("t_compute_s", 0) * 1e3,
        "t_memory_ms": rl.get("t_memory_s", 0) * 1e3,
        "t_collective_ms": rl.get("t_collective_s", 0) * 1e3,
        "dominant": rl.get("dominant"),
        "collective_per_chip": rl.get("collective_per_chip_bytes"),
        "temp_gb": mem.get("temp_size_in_bytes", 0) / 1e9,
        "args_gb": mem.get("argument_size_in_bytes", 0) / 1e9,
        "compile_s": r.get("compile_s"),
    }, indent=2))
    return 0 if r["status"] == "compiled" else 1


if __name__ == "__main__":
    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    # the virtual devices are CPU ones: never take a TPU that is present
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.exit(main())
