"""The one general traffic generator: reads a traffic file, returns the
requests of one run.

A traffic file (``traffic/<name>.json``) holds only parameters::

    {"loop": "closed", "clients": 32,
     "prompt": {"kind": "lognormal", "median": 256, "sigma": 0.7,
                "lo": 64, "hi": 1024},
     "output": {...}, "pool_size": 512, "pool_seed": 0}

    {"loop": "open", "lanes": 16,
     "arrivals": {"kind": "poisson", "rate": 0.5},     # or "bursty"
     "prompt": {...}, "output": {...}, "pool_size": 256, "pool_seed": 0,
     "preroll_s": 10}

The (prompt length, output length) pairs and the inter-arrival gaps are
drawn once from ``pool_seed``, in that order: the mix is one fixed schedule
of sizes and arrivals, and ``--seed`` draws only the token ids, so that two
seeds differ in content, not in the amount or order of work.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

import numpy as np

from .arrivals import bursty_arrivals, poisson_arrivals
from .lengths import LengthDist


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use (``stream``) of a run seed; any whole number,
    negative or above 64 bits included."""
    return np.random.default_rng([seed % (1 << 64), stream])


@dataclass
class Traffic:
    """The requests of one run, in the order the clients send them."""
    name: str
    spec: dict
    prompts: List[np.ndarray]            # token ids, int32
    max_new: List[int]
    gaps_s: Optional[np.ndarray] = None  # open loop: inter-arrival gaps

    @property
    def loop(self) -> str:
        return self.spec["loop"]

    def due_s(self) -> np.ndarray:
        """Open loop: due time of each request after the traffic starts."""
        return np.cumsum(self.gaps_s)


def load_spec(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    if spec.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return spec


def pool_lengths(spec: dict):
    """The traffic's fixed multiset of (prompt, output) lengths, in the
    order drawn from ``pool_seed``."""
    pool = np.random.default_rng(int(spec.get("pool_seed", 0)))
    n = int(spec["pool_size"])
    plens = LengthDist.from_json(spec["prompt"]).sample(n, pool)
    olens = LengthDist.from_json(spec["output"]).sample(n, pool)
    return plens, olens


def make_traffic(name: str, spec: dict, seed: int, vocab: int) -> Traffic:
    n = int(spec["pool_size"])
    plens, olens = pool_lengths(spec)
    gaps = None
    if spec["loop"] == "open":
        arr = spec["arrivals"]
        kind, rate = arr["kind"], float(arr["rate"])
        if kind == "poisson":
            times = poisson_arrivals(rate, n, seed=int(spec.get("pool_seed", 0)))
        elif kind == "bursty":
            kw = {k: arr[k] for k in ("burst_factor", "mean_burst",
                                      "mean_calm") if k in arr}
            times = bursty_arrivals(rate, n, seed=int(spec.get("pool_seed", 0)),
                                    **kw)
        else:
            raise ValueError(f"arrivals kind {kind!r}")
        gaps = np.diff(np.concatenate([[0.0], times]))

    ids = seed_rng(seed, 1)
    prompts = [ids.integers(0, vocab, size=int(k)).astype(np.int32) for k in plens]
    return Traffic(name, spec, prompts, [int(k) for k in olens], gaps)
