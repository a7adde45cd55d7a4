"""The sweep that sets an open-loop mix's rate: one run of the cell at each
of several rates, in one process, and whether the queue grows.

    python3 benchmarks/onchip/sweep.py --workload <cell> \\
        --rates 0.3,0.5,0.7 --seconds 60 --seed 1

For each rate one JSON line: requests submitted in the window, first
tokens delivered in it, requests still queued at its close, and the time
to first token over the window's first and second halves (a queue that
grows shows as a second half far above the first).  The highest rate that
shows no growth is the knee; the mix's file records 0.8 of it.  Needs a TPU.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "artifacts" / "jax_cache")
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from onchip.harness import load_cell, run_cell
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"sweep.py: needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    base = load_cell(args.workload, ROOT)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(base, traffic=copy.deepcopy(base.traffic))
        cell.traffic["arrivals"]["rate"] = rate
        out = run_cell(cell, args.seed, args.seconds, False,
                       t_process=time.perf_counter(), chip_kind=dev.device_kind,
                       log=lambda m: print(f"  {m}", flush=True))
        load = out.detail["load"]
        ft = np.asarray(load.pop("first_tokens"), float).reshape(-1, 2)
        half = ft[:, 0] <= args.seconds / 2
        q = lambda a, p: float(np.percentile(a, p)) if len(a) else None
        print(json.dumps({"rate": rate, **load, "first_tokens": len(ft),
                          "ttft_p50_first_half_s": q(ft[half, 1], 50),
                          "ttft_p50_second_half_s": q(ft[~half, 1], 50),
                          "ttft_p90_s": q(ft[:, 1], 90),
                          "metrics": {k: v["value"] for k, v in
                                      out.line["metrics"].items()},
                          "correct": out.line["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
