"""Prompt/output length distributions.

Copied from ``repro.workload.lengths.LengthDist`` so that the yardstick does
not move with the program: ``("fixed", n)``, ``("uniform", lo, hi)`` or
``("lognormal", mean, sigma)``, clamped to ``[lo_clip, hi_clip]``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np


@dataclass(frozen=True)
class LengthDist:
    kind: str                      # "fixed" | "uniform" | "lognormal"
    params: Tuple[float, ...]      # fixed: (n,); uniform: (lo, hi);
    #                                lognormal: (mean, sigma) of the value
    lo_clip: int = 2
    hi_clip: int = 1 << 30

    def __post_init__(self):
        kinds = ("fixed", "uniform", "lognormal")
        if self.kind not in kinds:
            raise ValueError(f"kind {self.kind!r} not in {kinds}")

    def sample(self, n: int, rng: np.random.Generator) -> np.ndarray:
        if self.kind == "fixed":
            out = np.full(n, self.params[0])
        elif self.kind == "uniform":
            lo, hi = self.params
            out = rng.integers(int(lo), int(hi) + 1, size=n).astype(float)
        else:
            mean, sigma = self.params
            # parametrized by the VALUE's mean, not the underlying normal's
            mu = np.log(max(mean, 1e-9)) - 0.5 * sigma * sigma
            out = rng.lognormal(mu, sigma, size=n)
        out = np.clip(np.rint(out), self.lo_clip, self.hi_clip)
        return out.astype(np.int64)

    @classmethod
    def from_json(cls, d: dict) -> "LengthDist":
        """``{"kind": "lognormal", "median": m, "sigma": s, "lo": a,
        "hi": b}``; a lognormal may give ``median`` in place of the mean."""
        kind = d["kind"]
        if kind == "lognormal":
            sigma = float(d["sigma"])
            mean = (float(d["mean"]) if "mean" in d
                    else float(d["median"]) * float(np.exp(0.5 * sigma ** 2)))
            params = (mean, sigma)
        elif kind == "uniform":
            params = (d["lo"], d["hi"])
        else:
            params = (d["n"],)
        return cls(kind, tuple(params), int(d.get("lo", 2)),
                   int(d.get("hi", 1 << 30)))
