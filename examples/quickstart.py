"""Quickstart: train a tiny draft/target pair on the synthetic corpus and
generate with TapOut sequence-level UCB1.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import get_corpus, trained_pair
from repro.core import EngineSpec, make_controller, make_engine
from repro.data.tokenizer import ByteTokenizer
from repro.launch.compile_cache import use_compile_cache


def main():
    print("== loading (or training) the llama-1b-8b analog pair ...")
    draft, target = trained_pair("llama-1b-8b")
    tok = ByteTokenizer()
    corpus = get_corpus()
    controller = make_controller("tapout_seq_ucb1", gamma_max=16)
    engine = make_engine(draft, target, controller,
                         EngineSpec(backend="single", max_len=1024))

    for kind, ids in corpus.prompts("humaneval", 2, seed=5):
        res = engine.generate(ids[:64], 96)
        text = tok.decode(res.tokens[res.prompt_len:])
        print(f"\n== prompt ({kind}) -> {res.new_tokens} tokens, "
              f"m={res.mean_accepted:.2f}, accept={res.accept_rate:.0%}, "
              f"{len(res.sessions)} sessions")
        print(text[:200].replace("\n", "\\n"))

    print("\n== learned arm values (interpretable bandit state):")
    for arm, v in zip(controller.arms, controller.arm_values):
        print(f"   {arm.name:16s} {v:.3f}   (pulls: "
              f"{controller.bandit.counts[list(controller.arms).index(arm)]})")


if __name__ == "__main__":
    use_compile_cache()
    main()
