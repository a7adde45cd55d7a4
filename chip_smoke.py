"""Smoke run of the serving path on a TPU: qwen2.5-3b at published widths.

Serves a few requests through ``SpecServer`` on the paged backend
(``EngineSpec`` -> scheduler -> paged block pools -> fused tick) with random
bf16 weights made from fixed seeds, checks what comes out, and prints a
one-line JSON verdict as the last line of standard output.  It needs a TPU:
on any other platform it exits non-zero before doing any work.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # sharded serving on a four-chip host

One chip: the target is ``get_config("qwen2.5-3b")`` (36 layers, d_model
2048, 16 heads / 2 KV heads, d_ff 11008, vocab 151936) and the draft is
``draft_config("qwen2.5-3b")``; nothing is cut.  After a warm-up pass that
compiles every program, 8 requests (prompts of 128-512 tokens, 32 new
tokens each) are served and checked: every request answered in full with
in-vocabulary ids, the bandit pulled arms, the block allocators conserve
their blocks, the target's logits on the server's paged path agree with a
fresh full forward (``T.step``) over the same tokens, and every served
token is that forward's greedy choice up to a near-tie.

Four chips: the same requests are served on one device, on a data-parallel
mesh (``make_host_mesh(data=4)``, tokens must be identical) and on a
data x tensor-parallel mesh (``make_host_mesh(data=2, model=2)``, paged-path
logits must agree within the same bounds; docs/sharding.md#numerics).

Every figure printed before the last line is output of one smoke run, not a
benchmark measurement.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import draft_config, get_config  # noqa: E402
from repro.core import EngineSpec, ModelBundle, make_controller  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving.engine import SpecServer  # noqa: E402

ARCH = "qwen2.5-3b"
CONTROLLER = "tapout_seq_ucb1"
GAMMA_MAX = 8
BATCH_SIZE = 8
BLOCK_SIZE = 64
TARGET_SEED, DRAFT_SEED, TRAFFIC_SEED, WARMUP_SEED = 0, 1, 2, 3
DRAIN_TIMEOUT_S = 600.0

# Logit agreement, served paged path vs a fresh full forward, both in bf16
# with the same weights, as fractions of the reference logits' standard
# deviation.  The two paths differ only in how the same bf16 numbers are
# grouped (chunked prefill into block pools and a verify window on one side,
# one dense pass on the other), so their gap is bf16 rounding.  Measured on
# the CPU at 36 layers x d_model 256 (same check, same code): bf16 KV gives
# an RMS of 1.5% and a max of 6.7%; an fp8 (e4m3) KV cache, one precision
# step below the configured bf16, gives 4.4% and 18%.  The bounds sit
# between the two: about twice the bf16 gap, and below the fp8 gap.
LOGIT_RMS_REL = 0.03
LOGIT_MAX_REL = 0.15
# Tensor-parallel serving all-reduces partial sums, which reorders the
# reductions of every sharded matmul (docs/sharding.md#numerics): another
# regrouping of the same bf16 numbers, held to the same bounds.
# A greedy server emits the argmax of its own logits.  Where those are
# within LOGIT_MAX_REL of the full forward's on every entry, the emitted
# token's reference logit is at most twice that below the reference top.
GREEDY_DEFICIT_REL = 2 * LOGIT_MAX_REL


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclass(frozen=True)
class Traffic:
    """``n`` requests with prompts of ``lo``..``hi`` tokens drawn from
    ``seed``, each asking for ``max_new`` new tokens."""
    n: int = 8
    lo: int = 128
    hi: int = 512
    max_new: int = 32
    seed: int = TRAFFIC_SEED

    def requests(self, vocab: int) -> List[Tuple[List[int], int]]:
        rng = np.random.default_rng(self.seed)
        lens = rng.integers(self.lo, self.hi + 1, size=self.n)
        return [(rng.integers(0, vocab, size=int(n)).tolist(), self.max_new)
                for n in lens]


class CompileClock:
    """Seconds and count of XLA backend compilations while open."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def init_bundle(cfg, seed: int, dtype=jnp.bfloat16) -> ModelBundle:
    init = jax.jit(T.init_params, static_argnums=(0,),
                   static_argnames=("dtype",))
    return ModelBundle(init(cfg, jax.random.PRNGKey(seed), dtype=dtype), cfg)


def param_bytes(bundle: ModelBundle) -> int:
    return sum(int(a.nbytes) for a in jax.tree.leaves(bundle.params))


def smoke_spec(traffic: Traffic, batch_size: int = BATCH_SIZE,
               mesh=None) -> EngineSpec:
    """Paged-backend spec sized so every slot can hold its worst case
    (prompt + budget + one session's draft overshoot): the pool never
    backpressures and no request is refused."""
    worst = traffic.hi + traffic.max_new + GAMMA_MAX + 2
    max_len = -(-worst // BLOCK_SIZE) * BLOCK_SIZE
    return EngineSpec(batch_size=batch_size, pool_tokens=batch_size * max_len,
                      block_size=BLOCK_SIZE, max_len=max_len,
                      cache_dtype=jnp.bfloat16, mesh=mesh)


def _drain(server: SpecServer, requests) -> Tuple[List, float]:
    rids = [server.submit(p, n) for p, n in requests]
    t0 = time.perf_counter()
    server.run_until_drained(timeout_s=DRAIN_TIMEOUT_S)
    wall = time.perf_counter() - t0
    by_rid = {r.request_id: r for r in server.responses}
    _require(all(rid in by_rid for rid in rids),
             f"unanswered requests: {[r for r in rids if r not in by_rid]}")
    return [by_rid[rid] for rid in rids], wall


def _paged_logits(params, cfg, tokens, lane, spec):
    logits, _ = T.paged_step(params, cfg, tokens, lane, spec,
                             all_logits=True)
    return logits


def paged_continuation_logits(server: SpecServer, seq: List[int],
                              prompt_len: int) -> np.ndarray:
    """Target logits on the server's paged path for a served continuation.

    Admits ``seq[:prompt_len]`` into a free slot through the engine's own
    admission (its chunked prefill into the block pools), then runs one
    ``paged_step`` of the target against the pool over
    ``seq[prompt_len - 1:-1]``.  Row ``j`` predicts ``seq[prompt_len + j]``.
    The slot is released afterwards."""
    eng = server.engine
    width = len(seq) - prompt_len
    slot = eng.free_slots()[0]
    eng.open_stream(slot, list(seq[:prompt_len]),
                    reserve_tokens=prompt_len + width)
    try:
        toks = np.asarray(seq[prompt_len - 1:prompt_len - 1 + width],
                          np.int32)[None]
        # a fresh jit per call: the models' sharding hints resolve against
        # the mesh active at trace time, which differs between servers
        with eng._mesh_ctx():
            lane = eng._lane_view(eng.tcache, slot)
            logits = jax.jit(_paged_logits, static_argnums=(1, 4))(
                eng.target.params, eng.target.cfg, jnp.asarray(toks), lane,
                eng.tspec)
        return np.asarray(logits[0], np.float32)
    finally:
        eng.close_stream(slot)


@functools.partial(jax.jit, static_argnums=(1, 4))
def _full_forward(params, cfg, tokens, start, rows):
    cache, spec = T.init_cache(cfg, 1, tokens.shape[1], jnp.bfloat16)
    logits, _ = T.step(params, cfg, tokens, cache, spec, all_logits=True)
    return jax.lax.dynamic_slice_in_dim(logits[0], start, rows, axis=0)


def reference_logits(target: ModelBundle, seq: List[int], prompt_len: int,
                     pad_to: int, rows: int) -> np.ndarray:
    """A fresh full forward (``T.step`` over every token from an empty
    dense cache) of ``seq[:-1]``; row ``j`` predicts ``seq[prompt_len + j]``.
    The tokens are padded at the end to ``pad_to`` and the rows taken are
    ``rows`` long, so every request shares one program (causal attention:
    the padding changes no row that is kept)."""
    toks = np.zeros((1, pad_to), np.int32)
    toks[0, :len(seq) - 1] = seq[:-1]
    with jax.default_matmul_precision("highest"):
        logits = _full_forward(target.params, target.cfg, jnp.asarray(toks),
                               prompt_len - 1, rows)
    return np.asarray(logits, np.float32)[:len(seq) - prompt_len]


def logit_gap(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """RMS and max of ``|got - want|``, absolute and relative to the
    standard deviation of ``want``."""
    d = np.abs(got.astype(np.float64) - want.astype(np.float64))
    std = float(want.std())
    rms, mx = float(np.sqrt((d ** 2).mean())), float(d.max())
    return {"rms": rms, "max_abs": mx, "ref_std": std,
            "rms_rel": rms / std, "max_rel": mx / std}


def logits_agree(gap: Dict[str, float], rms_rel: float,
                 max_rel: float) -> bool:
    return gap["rms_rel"] <= rms_rel and gap["max_rel"] <= max_rel


def check_allocators(server: SpecServer) -> None:
    """Conservation on both pools, and an idle server holds no blocks."""
    for name, alloc in (("draft", server.engine.dalloc),
                        ("target", server.engine.talloc)):
        _require(alloc.check_conservation(),
                 f"{name} block allocator violates conservation")
        _require(len(alloc.free) == alloc.num_blocks - 1,
                 f"{name} pool leaks {alloc.num_blocks - 1 - len(alloc.free)}"
                 " blocks after drain")


def check_responses(responses, requests, vocab: int, gamma_max: int) -> int:
    """Every request got at least the tokens it asked for (a final tick may
    overshoot by at most ``gamma_max``), all ids in the vocabulary.
    Returns the number of new tokens."""
    total = 0
    for r, (prompt, want) in zip(responses, requests):
        res = r.result
        _require(res.tokens[:len(prompt)] == list(prompt),
                 f"request {r.request_id}: prompt not preserved")
        n = res.new_tokens
        _require(want <= n <= want + gamma_max,
                 f"request {r.request_id}: {n} new tokens, asked {want}")
        out = np.asarray(res.tokens[len(prompt):])
        _require(bool(((out >= 0) & (out < vocab)).all()),
                 f"request {r.request_id}: token id outside [0, {vocab})")
        total += n
    return total


def serve_and_check(draft: ModelBundle, target: ModelBundle,
                    traffic: Traffic = Traffic(), *,
                    warmup: Optional[Traffic] = None,
                    batch_size: int = BATCH_SIZE) -> dict:
    """Serve ``traffic`` through ``SpecServer`` on the paged backend and
    check it.  ``warmup`` traffic is served first on the same server so
    that every program is compiled before the timed drain.  Raises
    ``SmokeFailure`` on any failed check; returns the smoke report."""
    vocab = target.cfg.vocab_size
    ctrl = make_controller(CONTROLLER, gamma_max=GAMMA_MAX, seed=0)
    server = SpecServer(draft, target, ctrl,
                        spec=smoke_spec(traffic, batch_size))
    _require(server.backend == "paged" and server.engine.fused,
             f"expected the fused paged backend, got {server.backend!r}")
    report = {"backend": server.backend, "fused": server.engine.fused}
    with CompileClock() as clock:
        if warmup is not None:
            t0 = time.perf_counter()
            _drain(server, warmup.requests(vocab))
            report["warmup_s"] = time.perf_counter() - t0
        report["warmup_compiles"] = clock.count
        report["warmup_compile_s"] = clock.seconds
        pulls0 = ctrl.bandit.counts.copy()
        requests = traffic.requests(vocab)
        responses, wall = _drain(server, requests)
        report["drain_compiles"] = clock.count - report["warmup_compiles"]
    pulls = ctrl.bandit.counts - pulls0
    report["arm_pulls"] = {a.name: int(n) for a, n in zip(ctrl.arms, pulls)}
    _require(int(pulls.sum()) > 0, "the bandit pulled no arm")
    report["new_tokens"] = check_responses(responses, requests, vocab,
                                           GAMMA_MAX)
    report["drain_s"] = wall
    acc = sum(r.result.total_accepted for r in responses)
    drf = sum(r.result.total_drafted for r in responses)
    report["accept_rate"] = acc / max(drf, 1)
    report["ticks"] = server.tick_count
    check_allocators(server)

    # logits: request 0's whole served continuation on the server's paged
    # path vs one fresh full forward over the same tokens
    refs = [reference_logits(target, r.result.tokens, len(p),
                             server.engine.max_len,
                             traffic.max_new + GAMMA_MAX)
            for r, (p, _) in zip(responses, requests)]
    seq, plen = responses[0].result.tokens, len(requests[0][0])
    got = paged_continuation_logits(server, seq, plen)
    check_allocators(server)
    gap = logit_gap(got, refs[0])
    report["logit_gap"] = gap
    _require(logits_agree(gap, LOGIT_RMS_REL, LOGIT_MAX_REL),
             f"paged-path logits differ from the full forward: {gap} "
             f"(bounds rms_rel {LOGIT_RMS_REL}, max_rel {LOGIT_MAX_REL})")
    # greedy tokens, every request: a served token may differ from the full
    # forward's argmax only where the two are near-tied, i.e. its reference
    # logit is within GREEDY_DEFICIT_REL of the top one
    flips = [dict(f, request=i) for i, (r, (p, _), ref)
             in enumerate(zip(responses, requests, refs))
             for f in greedy_flips(np.asarray(r.result.tokens[len(p):]), ref)]
    report["greedy_flips"] = flips
    _require(all(f["deficit"] <= GREEDY_DEFICIT_REL for f in flips),
             f"served tokens that are not greedy under the full forward: "
             f"{[f for f in flips if f['deficit'] > GREEDY_DEFICIT_REL]}")
    return report


def greedy_flips(served: np.ndarray, ref: np.ndarray) -> List[dict]:
    """Positions where a served (greedy) token is not the full forward's
    argmax, each with how far the served token's reference logit falls
    below the top one, in units of the reference logits' standard
    deviation."""
    std = float(ref.std())
    return [{"pos": int(i), "served": int(served[i]),
             "ref_argmax": int(ref[i].argmax()),
             "deficit": float(ref[i].max() - ref[i, served[i]]) / std}
            for i in np.flatnonzero(ref.argmax(-1) != served)]


# ----------------------------------------------------------------- 4 chips

def _bytes_in_use() -> List[Optional[int]]:
    """Per device; None where the backend keeps no statistics (CPU)."""
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()]


def _check_spread(name: str, run: dict) -> None:
    """A sharded server holds weights on every device, and every device
    that reports memory has some in use."""
    held = {d for leaf in jax.tree.leaves(run["server"].engine.target.params)
            for d in leaf.sharding.device_set}
    _require(len(held) == len(jax.devices()),
             f"{name}: target weights on {len(held)} of "
             f"{len(jax.devices())} devices")
    _require(all(b is None or b > 0 for b in run["bytes_in_use"]),
             f"{name} left a device empty: {run['bytes_in_use']}")


def _serve_on(draft_host, target_host, dcfg, tcfg, requests, traffic,
              mesh) -> dict:
    """One four-chip sub-phase: weights placed from host copies (one
    device, or the engine shards them over ``mesh``), every request
    served, request 0's paged-path verify-window logits taken."""
    if mesh is None:
        dev = jax.devices()[0]
        dparams = jax.device_put(draft_host, dev)
        tparams = jax.device_put(target_host, dev)
    else:
        dparams, tparams = draft_host, target_host
    ctrl = make_controller(CONTROLLER, gamma_max=GAMMA_MAX, seed=0)
    server = SpecServer(ModelBundle(dparams, dcfg), ModelBundle(tparams, tcfg),
                        ctrl, spec=smoke_spec(traffic, BATCH_SIZE, mesh))
    responses, wall = _drain(server, requests)
    new = check_responses(responses, requests, tcfg.vocab_size, GAMMA_MAX)
    check_allocators(server)
    return {"tokens": [r.result.tokens for r in responses],
            "server": server, "drain_s": wall, "new_tokens": new,
            "bytes_in_use": _bytes_in_use(), "ticks": server.tick_count}


def four_chip_phase(tcfg, dcfg, traffic: Traffic = Traffic()) -> List[str]:
    """Unsharded vs data=4 (identical tokens) vs data=2 x model=2 (logits
    within tolerance) for a (target, draft) config pair.  Returns the lines
    to print."""
    # weights made once on device 0 and kept on the host, so each
    # sub-phase's copy is freed with its server
    target_host = jax.device_get(init_bundle(tcfg, TARGET_SEED).params)
    draft_host = jax.device_get(init_bundle(dcfg, DRAFT_SEED).params)
    requests = traffic.requests(tcfg.vocab_size)
    plen = len(requests[0][0])
    lines = []

    def record(name, run, extra=""):
        lines.append(f"{name}: drain_s={run['drain_s']:.3f} "
                     f"new_tokens={run['new_tokens']} ticks={run['ticks']}"
                     f"{extra}")
        lines.append(f"{name}: bytes_in_use per device = "
                     f"{run['bytes_in_use']}")

    base = _serve_on(draft_host, target_host, dcfg, tcfg, requests, traffic,
                     None)
    base_logits = paged_continuation_logits(base["server"], base["tokens"][0], plen)
    record("one device", base)
    del base["server"]
    gc.collect()

    dp = _serve_on(draft_host, target_host, dcfg, tcfg, requests, traffic,
                   make_host_mesh(data=4))
    same = dp["tokens"] == base["tokens"]
    record("data=4", dp, f" tokens_identical={same}")
    _check_spread("data=4", dp)
    del dp["server"]
    gc.collect()
    _require(same, "data-parallel tokens differ from the one-device run")

    tp = _serve_on(draft_host, target_host, dcfg, tcfg, requests, traffic,
                   make_host_mesh(data=2, model=2))
    tp_logits = paged_continuation_logits(tp["server"], base["tokens"][0], plen)
    _check_spread("data=2 x model=2", tp)
    del tp["server"]
    gap = logit_gap(tp_logits, base_logits)
    n_same = sum(a == b for a, b in zip(tp["tokens"], base["tokens"]))
    record("data=2 x model=2", tp,
           f" requests_with_identical_tokens={n_same}/{len(requests)}"
           f" logit_gap={json.dumps(gap)}")
    _require(logits_agree(gap, LOGIT_RMS_REL, LOGIT_MAX_REL),
             f"tensor-parallel logits differ from one device: {gap}")
    return lines


# ------------------------------------------------------------------- main

def one_chip_phase() -> List[str]:
    tcfg, dcfg = get_config(ARCH), draft_config(ARCH)
    target = init_bundle(tcfg, TARGET_SEED)
    draft = init_bundle(dcfg, DRAFT_SEED)
    rep = serve_and_check(draft, target, Traffic(),
                          warmup=Traffic(seed=WARMUP_SEED, max_new=4))
    stats = jax.devices()[0].memory_stats() or {}
    return [
        f"model: {ARCH} target {tcfg.num_layers}L d_model={tcfg.d_model} "
        f"vocab={tcfg.vocab_size}; draft {dcfg.num_layers}L "
        f"d_model={dcfg.d_model}; bf16 weights and KV",
        f"backend: {rep['backend']} fused={rep['fused']} "
        f"batch_size={BATCH_SIZE} block_size={BLOCK_SIZE} "
        f"gamma_max={GAMMA_MAX}",
        f"param_bytes: target={param_bytes(target)} draft={param_bytes(draft)}",
        f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}",
        f"warmup: {rep['warmup_s']:.3f} s, of which compiling "
        f"{rep['warmup_compile_s']:.3f} s over {rep['warmup_compiles']} "
        "compilations",
        f"drain: {rep['drain_s']:.3f} s for {Traffic().n} requests, "
        f"{rep['ticks']} ticks in all, {rep['drain_compiles']} compilations "
        "inside the drain",
        f"new_tokens: {rep['new_tokens']}",
        f"accept_rate: {rep['accept_rate']:.4f}",
        f"arm_pulls: {json.dumps(rep['arm_pulls'])}",
        f"logit_gap vs full forward: {json.dumps(rep['logit_gap'])} "
        f"(bounds rms_rel {LOGIT_RMS_REL}, max_rel {LOGIT_MAX_REL})",
        f"greedy check: {rep['new_tokens']} served tokens against the "
        f"full forward, near-tie flips (bound {GREEDY_DEFICIT_REL}): "
        f"{json.dumps(rep['greedy_flips'])}",
    ]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve and check on one chip; 4: sharded "
                    "serving against one device, and nothing else")
    args = ap.parse_args(argv)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r} "
              f"({len(devices)} device(s)). There is no CPU fallback.",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    print(f"smoke run, not a benchmark: device_kind={kind!r} "
          f"device_count={len(devices)}", flush=True)
    if args.chips == 4:
        lines = four_chip_phase(get_config(ARCH), draft_config(ARCH))
    else:
        lines = one_chip_phase()
    for line in lines:
        print(f"smoke: {line}", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind,
                                             "count": len(devices)}}),
          flush=True)
    return 0


if __name__ == "__main__":
    use_compile_cache()
    sys.exit(main())
