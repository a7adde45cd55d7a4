"""End-to-end serving driver (the paper's deployment shape): a batched
request stream served with speculative decoding + TapOut, bandit shared
online across requests.  Compares against Static-6 on the same workload.

    PYTHONPATH=src python examples/serve_tapout.py [--requests 12]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import get_corpus, trained_pair
from repro.core import EngineSpec, StaticGamma, make_controller
from repro.launch.compile_cache import use_compile_cache
from repro.serving.engine import SpecServer


def serve(controller, draft, target, prompts, max_new):
    srv = SpecServer(draft, target, controller,
                     spec=EngineSpec(batch_size=4, max_len=1024))
    for ids in prompts:
        srv.submit(ids, max_new)
    srv.run_until_drained()
    return srv.throughput_stats()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=64)
    args = ap.parse_args()

    draft, target = trained_pair("llama-1b-8b")
    corpus = get_corpus()
    # a shifting workload: code first, then prose (tests online adaptation)
    prompts = [ids[:48] for _, ids in
               corpus.prompts("humaneval", args.requests // 2, seed=3)]
    prompts += [ids[:48] for _, ids in
                corpus.prompts("mt_bench", args.requests - len(prompts), seed=4)]

    tap = make_controller("tapout_seq_ucb1", gamma_max=16)
    s_tap = serve(tap, draft, target, prompts, args.max_new)
    s_sta = serve(StaticGamma(gamma=6), draft, target, prompts, args.max_new)

    print(f"{'':24s}{'TapOut Seq-UCB1':>18s}{'Static-6':>12s}")
    for k in ("total_new_tokens", "accept_rate", "modeled_cost_per_token",
              "wall_s_per_token", "mean_latency_s"):
        print(f"{k:24s}{s_tap[k]:>18.4g}{s_sta[k]:>12.4g}")
    speedup = s_sta["modeled_cost_per_token"] / s_tap["modeled_cost_per_token"]
    print(f"\nmodeled speedup over Static-6: {speedup:.2f}x")
    print("final arm values:", dict(zip([a.name for a in tap.arms],
                                        [round(float(v), 3) for v in tap.arm_values])))


if __name__ == "__main__":
    use_compile_cache()
    main()
