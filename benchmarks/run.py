"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the headline metric
or claim check of each benchmark) and writes full JSON payloads under
artifacts/bench/.

  fig2_entropy        Fig. 2   entropy by position, coding vs non-coding
  table2_reward       Table 2  r_simple vs r_blend (+ Fig. 3 lengths)
  fig4_ucb_variants   Fig. 4   UCB1 vs UCB-Tuned
  table3_main         Table 3  methods x pairs x {MT-Bench, HumanEval}
  table4_specdecpp    Table 4  trained SpecDec++ vs bandits
  table5_specbench    Table 5  SpecBench across pairs
  a2_more_arms        App. A.2 small vs multi-threshold arm pool
  serving_batch       —        batched serving tokens/s + latency vs B
  tree_spec           —        tree-vs-chain accepted/verify + shape bandit
  quant_spec          —        bf16 vs int8-KV vs int8-draft arms + pool bytes
  prefix_sharing      —        shared-prefix pool blocks / concurrency / TTFT
  slo_serving         —        open-loop goodput under p95 SLO, FIFO vs SLO
  drafters            —        heterogeneous drafter pool: fixed vs meta-bandit
  moe_encoder         —        MoE routed-cost + shared encoder-segment pool
  kernels_micro       —        kernel/XLA-path microbench
  roofline            §Roofline collation from the dry-run artifacts

Serving-path benches (serving_batch, tree_spec, quant_spec,
prefix_sharing, slo_serving, drafters, moe_encoder) additionally append their
summaries to the repo-root BENCH_serving.json (committed — the perf
trajectory across
PRs); ``scripts/check_bench_schema.py`` validates every appended row.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced prompt counts / pairs")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    from . import (bench_arm_values, bench_drafters, bench_entropy,
                   bench_kernels, bench_main, bench_moe_encoder,
                   bench_more_arms, bench_prefix_sharing, bench_quant,
                   bench_reward, bench_serving_batch, bench_specbench,
                   bench_specdecpp, bench_tree, bench_ucb_variants,
                   roofline_table)

    def derived_fmt(d):
        keys = [k for k in d if k.startswith("claim_")]
        if keys:
            return ";".join(f"{k}={d[k]}" for k in keys)
        return ""

    benches = {
        "fig2_entropy": (bench_entropy.run, derived_fmt),
        "table2_reward": (bench_reward.run, derived_fmt),
        "fig4_ucb_variants": (bench_ucb_variants.run, derived_fmt),
        "table3_main": (bench_main.run, derived_fmt),
        "table4_specdecpp": (bench_specdecpp.run, derived_fmt),
        "table5_specbench": (bench_specbench.run, derived_fmt),
        "a2_more_arms": (bench_more_arms.run, derived_fmt),
        "serving_batch": (bench_serving_batch.run, derived_fmt),
        "tree_spec": (bench_tree.run, derived_fmt),
        "quant_spec": (bench_quant.run, derived_fmt),
        "prefix_sharing": (bench_prefix_sharing.run, derived_fmt),
        "drafters": (bench_drafters.run, derived_fmt),
        "moe_encoder": (bench_moe_encoder.run, derived_fmt),
        "fig5_6_arm_values": (bench_arm_values.run, lambda d: ";".join(
            f"{k}_spearman={d[k]['spearman_values_vs_speedup']:.2f}"
            for k in d)),
        "kernels_micro": (bench_kernels.run, lambda d: ";".join(
            f"{k}={v:.1f}" for k, v in d.items() if k.endswith("_us"))),
        "roofline": (roofline_table.run, lambda d:
                     f"compiled={d['n_compiled_scanned']}/{d['n_total_scanned']}"),
    }
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    rc = 0
    for name, (fn, fmt) in benches.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            payload = fn(quick=args.quick)
            us = (time.perf_counter() - t0) * 1e6
            print(f"{name},{us:.0f},{fmt(payload)}", flush=True)
        except Exception as e:
            traceback.print_exc()
            print(f"{name},-1,ERROR:{type(e).__name__}", flush=True)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
