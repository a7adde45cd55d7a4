"""Speculative decoding with TapOut on ANY assigned architecture family:
instantiates the reduced same-family target + an even smaller draft and runs
dynamic speculation — including the attention-free (SSM / RG-LRU) families
via the snapshot-recompute rollback path.

    PYTHONPATH=src python examples/arch_spec_decode.py --arch mamba2-1.3b
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs.registry import ARCH_IDS, smoke_config
from repro.core import EngineSpec, ModelBundle, make_controller, make_engine
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="mamba2-1.3b")
    ap.add_argument("--max-new", type=int, default=48)
    args = ap.parse_args()

    tcfg = smoke_config(args.arch).replace(vocab_size=259)
    dcfg = tcfg.replace(name=tcfg.name + "-draft", d_model=max(64, tcfg.d_model // 2),
                        num_heads=max(1, tcfg.num_heads // 2),
                        num_kv_heads=1 if tcfg.num_kv_heads == 1 else
                        max(1, tcfg.num_kv_heads // 2),
                        d_ff=max(64, tcfg.d_ff // 2) if tcfg.d_ff else 0)
    # (random weights — this demonstrates the mechanics, not quality)
    target = ModelBundle(T.init_params(tcfg, jax.random.PRNGKey(0)), tcfg)
    draft = ModelBundle(T.init_params(dcfg, jax.random.PRNGKey(1)), dcfg)
    ctrl = make_controller("tapout_seq_ucb1", gamma_max=8)
    eng = make_engine(draft, target, ctrl,
                      EngineSpec(backend="single", max_len=256))
    print(f"arch family: {tcfg.arch_type}; pointer-rollback caches: "
          f"draft={eng.draft_cheap} target={eng.target_cheap}")
    kw = {}
    res = eng.generate([1, 5, 9, 13, 17, 21], args.max_new)
    print(f"generated {res.new_tokens} tokens in {len(res.sessions)} sessions; "
          f"m={res.mean_accepted:.2f} accept={res.accept_rate:.0%}")
    print("arm values:", [round(float(v), 3) for v in ctrl.arm_values])


if __name__ == "__main__":
    use_compile_cache()
    main()
