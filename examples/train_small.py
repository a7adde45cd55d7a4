"""Train a small LM (any assigned --arch family, reduced) for a few hundred
steps on the synthetic corpus — exercises the full training substrate
(optimizer, chunked CE, remat, checkpointing).

    PYTHONPATH=src python examples/train_small.py --arch gemma-2b --steps 200
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs.registry import ARCH_IDS, smoke_config
from repro.data.synthetic import SyntheticCorpus
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.training.checkpoint import save_checkpoint
from repro.training.optimizer import OptConfig
from repro.training.train_loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma-2b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cfg = smoke_config(args.arch).replace(vocab_size=259)
    print(f"arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"params={cfg.param_count()/1e6:.1f}M")
    corpus = SyntheticCorpus(seed=0)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    out = train(cfg, params,
                corpus.training_batches(seq_len=args.seq_len,
                                        batch_size=args.batch, seed=1),
                OptConfig(lr=3e-3, warmup_steps=30, total_steps=args.steps),
                steps=args.steps, log_every=20,
                callback=lambda m: print(
                    f"step {m['step']:4d}  loss {m['loss']:.3f}  "
                    f"lr {m['lr']:.2e}  gnorm {m['grad_norm']:.2f}"))
    if args.out:
        save_checkpoint(args.out, out["params"],
                        {"arch": args.arch, "steps": args.steps})
        print("saved", args.out)


if __name__ == "__main__":
    use_compile_cache()
    main()
