"""TapOut arm pool: parameter- and training-free dynamic speculation rules.

Each arm is a JAX-traceable function ``fn(sig) -> stop (bool scalar/array)``
evaluated inside the jitted drafting while-loop via ``lax.switch``.  The
signal dict is computed once per drafted token from the draft distribution.

Paper Table 1 (thresholds are FIXED, not tuned — that is the point):

  Max-Confidence    p(top1) < 0.8
  SVIP              sqrt(H(p)) > 0.6
  AdaEDL            1 - sqrt(g_coef * H(p)) < lambda_t    (lambda_t online)
  SVIP-Difference   sqrt(H_t) - sqrt(H_{t-1}) > 0.2
  Logit-Margin      p(top1) - p(top2) <= 0.2
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

# ------------------------------------------------------------ signals


def signals_from_probs(probs, prev_sqrt_entropy, lam, pos):
    """probs: (..., V) draft distribution for the token just drafted.

    ``top1``/``top2`` are the two largest entries, as ``lax.top_k(p, 2)``
    gives them (a tied maximum gives ``top2 == top1``), found by
    reductions over the vocabulary: the TPU compiler lowers ``top_k``
    inside the draft loop to a full sort of it."""
    with jax.named_scope("signals"):
        p = probs.astype(jnp.float32)
        ent = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=-1)
        top1 = jnp.max(p, axis=-1)
        first = jnp.argmax(p, axis=-1)[..., None]
        hole = jnp.arange(p.shape[-1]) == first
        top2 = jnp.max(jnp.where(hole, -jnp.inf, p), axis=-1)
        return {
            "entropy": ent,
            "sqrt_entropy": jnp.sqrt(jnp.maximum(ent, 0.0)),
            "prev_sqrt_entropy": prev_sqrt_entropy,
            "top1": top1,
            "top2": top2,
            "lam": lam,
            "pos": pos,
        }


SIGNAL_VECTOR_DIM = 6


def signal_vector(sig) -> jnp.ndarray:
    """(..., 6) numeric feature view of the signal dict (classifier input
    for SpecDec++ and the per-position trace the engine can record)."""
    pos = jnp.asarray(sig["pos"], jnp.float32)
    parts = [sig["entropy"], sig["sqrt_entropy"], sig["top1"], sig["top2"],
             sig["top1"] - sig["top2"],
             jnp.broadcast_to(pos / 32.0, jnp.shape(sig["entropy"]))]
    return jnp.stack([jnp.asarray(x, jnp.float32) for x in parts], axis=-1)


# ------------------------------------------------------------ arms

@dataclass(frozen=True)
class Arm:
    name: str
    fn: Callable        # sig -> stop bool
    # NOTE: None (not nan) for threshold-free arms — nan breaks dataclass
    # __eq__ and would defeat the jit static-arg cache.
    threshold: Optional[float] = None


@functools.lru_cache(maxsize=None)
def _max_confidence(h: float):
    return lambda sig: sig["top1"] < h


@functools.lru_cache(maxsize=None)
def _svip(h: float):
    return lambda sig: sig["sqrt_entropy"] > h


@functools.lru_cache(maxsize=None)
def _adaedl(g_coef: float):
    return lambda sig: (1.0 - jnp.sqrt(jnp.maximum(
        g_coef * sig["entropy"], 0.0))) < sig["lam"]


@functools.lru_cache(maxsize=None)
def _svip_difference(h: float):
    return lambda sig: (sig["sqrt_entropy"] - sig["prev_sqrt_entropy"]) > h


@functools.lru_cache(maxsize=None)
def _logit_margin(h: float):
    return lambda sig: (sig["top1"] - sig["top2"]) <= h


# AdaEDL online-threshold hyperparameters (its own paper's defaults; these
# are part of the AdaEDL *rule*, not tuned per-dataset).
ADAEDL_DEFAULTS = dict(g_coef=1.0, lam_init=0.4, beta1=0.9, beta2=0.9,
                       eps=0.02, alpha_target=0.8)


@functools.lru_cache(maxsize=None)
def _default_pool_cached():
    return (
        Arm("max_confidence", _max_confidence(0.8), 0.8),
        Arm("svip", _svip(0.6), 0.6),
        Arm("adaedl", _adaedl(ADAEDL_DEFAULTS["g_coef"])),
        Arm("svip_difference", _svip_difference(0.2), 0.2),
        Arm("logit_margin", _logit_margin(0.2), 0.2),
    )


def default_pool() -> List[Arm]:
    """The paper's 5-arm pool with Table-1 thresholds (singleton arms so
    jit static-arg caches hit across controller instances)."""
    return list(_default_pool_cached())


@functools.lru_cache(maxsize=None)
def _multi_pool_cached():
    pool = []
    for h in (0.6, 0.8, 0.9):
        pool.append(Arm(f"max_confidence_{h}", _max_confidence(h), h))
    for h in (0.2, 0.4, 0.6):
        pool.append(Arm(f"svip_{h}", _svip(h), h))
    pool.append(Arm("adaedl", _adaedl(ADAEDL_DEFAULTS["g_coef"])))
    for h in (0.1, 0.2, 0.3):
        pool.append(Arm(f"svip_difference_{h}", _svip_difference(h), h))
    for h in (0.1, 0.2, 0.3):
        pool.append(Arm(f"logit_margin_{h}", _logit_margin(h), h))
    return tuple(pool)


def multi_threshold_pool() -> List[Arm]:
    """Appendix A.2 ablation: several thresholds per heuristic (worse)."""
    return list(_multi_pool_cached())


def pool_from_thresholds(th: Dict[str, float]) -> List[Arm]:
    """Build the 5-arm pool with explicit thresholds (used with the
    scale-free quantile calibration — see DESIGN.md §6: signal quantiles on
    a few calibration drafts, NO performance feedback, so the pool remains
    tuning-free in the paper's sense). Thresholds are rounded so the
    lru-cached arm makers (and therefore jit static-arg caches) hit."""
    r = lambda x: round(float(x), 4)
    return [
        Arm("max_confidence", _max_confidence(r(th["max_confidence"])), r(th["max_confidence"])),
        Arm("svip", _svip(r(th["svip"])), r(th["svip"])),
        Arm("adaedl", _adaedl(ADAEDL_DEFAULTS["g_coef"])),
        Arm("svip_difference", _svip_difference(r(th["svip_difference"])), r(th["svip_difference"])),
        Arm("logit_margin", _logit_margin(r(th["logit_margin"])), r(th["logit_margin"])),
    ]


@functools.lru_cache(maxsize=None)
def arm_by_name(name: str, threshold: float = None) -> Arm:
    """Single heuristic (for the tuned-baseline comparisons)."""
    makers = {
        "max_confidence": _max_confidence,
        "svip": _svip,
        "svip_difference": _svip_difference,
        "logit_margin": _logit_margin,
    }
    if name == "adaedl":
        return Arm("adaedl", _adaedl(ADAEDL_DEFAULTS["g_coef"]))
    defaults = {"max_confidence": 0.8, "svip": 0.6, "svip_difference": 0.2,
                "logit_margin": 0.2}
    h = defaults[name] if threshold is None else threshold
    return Arm(name, makers[name](h), h)


# ------------------------------------------------------------ shape arms

@dataclass(frozen=True)
class ShapeArm:
    """A SPECULATION-SHAPE arm for the tree meta-bandit: either a linear
    chain governed by one of the parameter-free stop rules above, or a
    static draft-tree topology (``core.tree.TreeSpec``) — at a DRAFT
    PRECISION (``bf16`` or ``int8`` weights, ``models/quant.py``).  The
    TapOut meta-algorithm is unchanged — shape and precision are just
    arm dimensions chosen from observed reward, no hand-tuned thresholds
    added; precision additionally scales the arm's modeled cost
    (``core.rewards.precision_cost_factor``)."""
    name: str
    kind: str                      # "chain" | "tree"
    stop: Optional[Arm] = None     # chain: dynamic stop rule
    tree: Optional[object] = None  # tree: TreeSpec (hashable)
    precision: str = "bf16"        # draft weight precision: "bf16" | "int8"
    # DRAFTER identity axis: which model drafts.  "" = the engine's default
    # draft bundle (every pre-pool shape arm), otherwise a name resolved by
    # the engine's DrafterPool (core/drafters.py).  ``drafter_cost`` is the
    # drafter's modeled per-token draft cost RELATIVE to the pool default
    # (e.g. an EAGLE head reusing the target's embeddings is far cheaper
    # than a standalone small transformer).
    drafter: str = ""
    drafter_cost: float = 1.0

    def __post_init__(self):
        assert (self.kind == "chain") == (self.stop is not None)
        assert (self.kind == "tree") == (self.tree is not None)
        assert self.precision in ("bf16", "int8"), self.precision
        assert self.drafter_cost > 0.0, self.drafter_cost


def chain_shape(stop: Arm) -> ShapeArm:
    return ShapeArm(f"chain_{stop.name}", "chain", stop=stop)


def tree_shape(tree) -> ShapeArm:
    return ShapeArm(f"tree_{tree.name}", "tree", tree=tree)


def quantized_shape(shape: ShapeArm) -> ShapeArm:
    """The int8-draft variant of a shape arm (same stop rule / topology,
    cheaper modeled cost)."""
    import dataclasses
    assert shape.precision == "bf16", f"{shape.name} already quantized"
    return dataclasses.replace(shape, name=f"{shape.name}_int8",
                               precision="int8")


def drafter_shape(shape: ShapeArm, drafter: str,
                  cost: float = 1.0) -> ShapeArm:
    """Bind a shape arm to a named drafter from a ``DrafterPool`` — the
    (drafter, shape) cross that makes drafter identity an arm dimension.
    ``cost`` is the drafter's per-token draft cost relative to the pool
    default (rounded so equal-cost pools produce identical, jit-static
    hashable arms)."""
    import dataclasses
    assert not shape.drafter, f"{shape.name} already bound to a drafter"
    return dataclasses.replace(shape, name=f"{shape.name}@{drafter}",
                               drafter=drafter,
                               drafter_cost=round(float(cost), 6))


def shape_cost_factor(shape: ShapeArm, gamma_max: int = 0) -> float:
    """Relative modeled DRAFT cost of a shape arm: the precision factor,
    times the drafter's relative cost, times the tree's node count relative
    to ``gamma_max`` for tree arms — a tree drafting 2x gamma_max nodes per
    session costs ~2x a full chain, and the cost-adjusted reward must see
    that, not just the precision axis.  (Chains draft a DYNAMIC number of
    tokens <= gamma_max; their per-session cost is the baseline 1.0 — the
    stop rule's thrift already shows up in the observed reward.)"""
    from .rewards import precision_cost_factor
    factor = precision_cost_factor(shape.precision) * shape.drafter_cost
    if shape.kind == "tree" and gamma_max:
        factor *= shape.tree.n_nodes / gamma_max
    return factor


def default_shape_pool(gamma_max: int = 8,
                       quantized: bool = False) -> List[ShapeArm]:
    """Chain arms (the paper pool's rules, unchanged) + tree topologies
    sized so no tree drafts more than ~2x gamma_max nodes.
    ``quantized=True`` additionally offers every chain rule at int8 draft
    precision (the memory-bound cost axis) — engines then hold one
    quantized copy of the draft weights next to the bf16 copy."""
    from . import tree as _t
    chains = [chain_shape(a) for a in default_pool()]
    shapes = list(chains)
    trees = [_t.binary(3), _t.wide(4, max(2, min(4, gamma_max // 2))),
             _t.from_branching((4, 2, 1))]
    shapes += [tree_shape(t) for t in trees if t.n_nodes <= 2 * gamma_max + 8]
    if quantized:
        shapes += [quantized_shape(s) for s in chains]
    return shapes


# Modeled relative per-token draft costs for the standard heterogeneous
# pool when no DrafterPool supplies measured ones: the default KV drafter
# is the 1.0 baseline; an EAGLE-style head is one transformer block plus a
# reused LM head; a tiny Mamba2/SSD draft sits in between (no KV reads but
# a full, if small, model).
DEFAULT_DRAFTER_COSTS = (("kv", 1.0), ("eagle", 0.3), ("ssd", 0.6))


def default_drafter_pool(gamma_max: int = 8,
                         drafters=DEFAULT_DRAFTER_COSTS) -> List[ShapeArm]:
    """The heterogeneous-drafter arm pool: the paper's 5 chain stop rules
    CROSSED with N candidate drafters, so the TapOut meta-bandit picks
    (drafter, stop rule) jointly from observed reward.  ``drafters`` is a
    sequence of ``(name, relative_cost)`` pairs (or a dict) — pass
    ``DrafterPool.shape_pool()`` arguments for measured costs.  Chains
    only: drafter switching rides the batched chain engine's fused tick."""
    if isinstance(drafters, dict):
        drafters = tuple(drafters.items())
    chains = [chain_shape(a) for a in default_pool()]
    return [drafter_shape(c, name, cost)
            for name, cost in drafters for c in chains]


def update_adaedl_lambda(lam: float, accept_rate_ema: float, n_acc: int,
                         n_drafted: int, *, beta1=None, beta2=None, eps=None,
                         alpha_target=None) -> Tuple[float, float]:
    """AdaEDL's post-draft threshold update (Appendix A.1).

    Returns (new_lambda, new_accept_rate_ema)."""
    d = ADAEDL_DEFAULTS
    beta1 = d["beta1"] if beta1 is None else beta1
    beta2 = d["beta2"] if beta2 is None else beta2
    eps = d["eps"] if eps is None else eps
    alpha_target = d["alpha_target"] if alpha_target is None else alpha_target
    r = n_acc / max(n_drafted, 1)
    ema = beta1 * accept_rate_ema + (1 - beta1) * r
    sign = 1.0 if (alpha_target - r) > 0 else (-1.0 if (alpha_target - r) < 0 else 0.0)
    lam = beta2 * lam + (1 - beta2) * (lam + eps * sign)
    return float(min(max(lam, 0.0), 1.0)), float(ema)
