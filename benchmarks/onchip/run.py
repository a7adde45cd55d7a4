"""The on-chip benchmark's one command: one run of one cell.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Builds the cell's server from the files ``BENCHMARK.json`` names, warms up
every shape the cell uses, measures ``--seconds`` seconds of traffic, checks
what the window served against the plain reference, and prints one JSON
line last: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end
with ``--trace 0``, per-layer with ``--trace 1``), ``device`` and, traced,
``breakdown``; then ``checks``, each number compared beside its limit.
It needs a TPU with as many chips as the cell asks for: anywhere else it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# a fixed directory inside the checkout: the path is part of the cache key
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "artifacts" / "jax_cache")
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks"))


def _say(msg: str) -> None:
    print(f"run.py: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import jax
        from onchip.harness import BenchError, load_cell, run_cell
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        _say(f"cannot import the system under test: {e}")
        return 2
    use_compile_cache()
    # every program, small ones too, goes to the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        cell = load_cell(args.workload, ROOT)
    except (BenchError, OSError, KeyError, ValueError) as e:
        _say(f"cannot load workload {args.workload!r}: {e}")
        return 2
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        _say(f"needs a TPU; JAX found platform {platform!r} "
             f"({len(devices)} device(s)). There is no CPU fallback.")
        return 2
    if len(devices) < cell.chips:
        _say(f"{cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
        return 2
    kind = devices[0].device_kind
    print(f"device: platform={platform} kind={kind!r} count={len(devices)}",
          flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_process=T_PROCESS, chip_kind=kind,
                   log=lambda m: print(m, flush=True))
    print(f"detail: {json.dumps(out.detail)}", flush=True)
    for line in out.stderr_tail:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
