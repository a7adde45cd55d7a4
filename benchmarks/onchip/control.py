"""Readings for the limit of ``served_gap``: the program's own gap and that
of the lower-precision controls, on several seeds of one cell, in one
process (set-up is long; the compiled programs are shared).

    python3 benchmarks/onchip/control.py --workload <cell> \\
        --seeds 1,2,3 --seconds <s>

Each seed is a whole run of the cell (the same window, sample and
reference as ``run.py``), plus the reference recomputed with every matmul
weight rounded to fp8 (e4m3) and to int8, one scale per output channel:
the control reads the gap of the token that precision puts first at each
served position.  One JSON line per seed.  The benchmark's own runs never
run the controls.  Needs a TPU.
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
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "artifacts" / "jax_cache")
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks"))

CONTROLS = ("fp8", "int8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    from onchip.harness import load_cell, run_cell
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control.py: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    cell = load_cell(args.workload, ROOT)
    t = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, seed, args.seconds, False, t_process=t,
                       chip_kind=dev.device_kind, controls=CONTROLS,
                       log=lambda m: print(f"  {m}", flush=True))
        chk = out.detail["check"]
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": out.line["correct"], **chk["gaps"],
                          "served_tokens": chk["served_tokens"],
                          "requests": len(chk["sample"]),
                          "metrics": {k: v["value"] for k, v in
                                      out.line["metrics"].items()}}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
