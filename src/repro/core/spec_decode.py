"""Jitted speculative-decoding primitives: dynamic-stop drafting + parallel
verification with exact speculative sampling (Leviathan et al. 2023).

Device/host split (DESIGN.md §3): the drafting while-loop (with the stopping
heuristic evaluated via ``lax.switch`` on a traced arm index) and the
verification forward are single jitted programs; the bandit update and
sequence assembly run on host between sessions.

Cache invariant used throughout: ``cache["pos"] == len(generated_seq) - 1``
— the final token of the sequence has not been fed to the model yet.

Two entry points per primitive:

* ``draft_session`` / ``verify_session`` — the single-stream programs
  (leading dim B over LOCKSTEP rows sharing one cache position).
* ``draft_session_batched`` / ``verify_session_batched`` — ONE jitted
  program serving B independent streams at different sequence positions:
  the single-stream core is ``vmap``-ped over a leading stream axis
  (stacked caches carry per-stream ``pos``), with per-stream arm indices,
  per-stream RNG and a per-stream ``active`` mask.  Outputs of inactive
  (finished/empty) slots are zeroed on device so the host never has to
  special-case them; their cache lanes are reconciled by the engine's
  batched rollback.

And a third pair for the PAGED cache (``models/cache.py``):

* ``draft_session_paged`` / ``verify_session_paged`` — BATCH-NATIVE cores
  over the shared block pool.  vmap cannot serve here: every lane writes
  into ONE pool (its own pages), and per-lane functional updates of a
  shared buffer do not compose under vmap.  Instead the model step itself
  is batched (``transformer.paged_step``: per-stream positions via block
  tables + lengths), the per-stream arm dispatch evaluates every arm on
  the batch and selects per row (what vmap-of-``lax.switch`` lowers to
  anyway), and sampling uses per-row PRNG keys.  Inactive lanes are forced
  ``stopped`` from step 0 and their writes land in the reserved trash
  block, so a masked lane can never touch a neighbor's pages.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models.cache import CacheSpec, paged_rollback, rollback
from repro.models.sharding import BATCH_AXES, constrain, resolve_spec
from .arms import Arm, SIGNAL_VECTOR_DIM, signal_vector, signals_from_probs

# static_argnames of the session primitives — shared with the per-engine
# re-jits below so an engine can rebuild a primitive without restating them
DRAFT_STATICS = ("cfg", "spec", "gamma_max", "temperature", "arms",
                 "n_prompt_tokens")
VERIFY_STATICS = ("cfg", "spec", "gamma_max", "temperature", "greedy")


def _lane_constrain(*arrays):
    """Pin the leading STREAM-LANE axis of flat (B, ...) session tensors to
    the ("pod","data") batch axes.  A no-op without an active mesh; under a
    mesh this keeps per-lane inputs/outputs resident with their lane's
    shard instead of letting GSPMD replicate them."""
    return tuple(constrain(a, BATCH_AXES) for a in arrays)


class DraftResult(NamedTuple):
    tokens: jnp.ndarray        # (B, gamma_max) int32 (padded with 0)
    n_drafted: jnp.ndarray     # (B,) int32
    qprobs: jnp.ndarray        # (B, gamma_max, V) draft distributions
    cache: dict                # draft cache AFTER drafting
    entropies: jnp.ndarray     # (B, gamma_max) sqrt-entropy per position (diag)
    signals: jnp.ndarray       # (B, gamma_max, 6) per-position signal vector


class VerifyResult(NamedTuple):
    n_accepted: jnp.ndarray    # (B,) accepted DRAFT tokens m <= n_drafted
    out_tokens: jnp.ndarray    # (B, gamma_max+1) accepted + replacement/bonus
    n_out: jnp.ndarray         # (B,) = m + 1
    cache: dict                # target cache AFTER verify forward (pos NOT rolled back)


def _sample(logits, rng, temperature: float):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits / temperature, axis=-1).astype(jnp.int32)


def _probs(logits, temperature: float):
    if temperature == 0.0:
        # the exact t -> 0 limit: one-hot at the argmax.  A softmax at a tiny
        # temperature scales the logits to ~1e4-1e5; on the TPU its argmax
        # then came out as token 0 for rows whose logits had a clear max.
        return jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                              dtype=jnp.float32)
    t = max(temperature, 1e-4)
    return jax.nn.softmax(logits.astype(jnp.float32) / t, axis=-1)


# ------------------------------------------------------------------ draft

def _run_draft_loop(step_fn, eval_stop, split_fn, sample_fn, cache,
                    in_tokens, rng, *, B: int, V: int, gamma_max: int,
                    temperature: float, force_stop=None):
    """THE dynamic-stop drafting loop, shared by every session flavor.

    The dense single-stream core, its vmapped batched wrapper and the
    batch-native paged core all run this exact body; they differ only in
    the injected callables:

      step_fn(tokens, cache) -> (logits, cache)     model advance
      eval_stop(i, sig_probs, prev_ent)
          -> (stop (B,), ent (B,), sigvec (B, 6))   arm dispatch
      split_fn(rng) -> (rng, key)                    PRNG split
      sample_fn(logits, key) -> (B,) int32           token sampling
      force_stop: (B,) bool — lanes forced stopped from step 0 (masked
          paged lanes; their writes land in the trash block).
    """
    # feed the known suffix; logits for the first drafted token
    logits, cache = step_fn(in_tokens, cache)
    rng, k0 = split_fn(rng)
    probs0 = _probs(logits[:, -1], temperature)
    sig_probs0 = _probs(logits[:, -1], 1.0)   # signals use the raw dist
    tok0 = sample_fn(logits[:, -1], k0)

    tokens_buf = jnp.zeros((B, gamma_max), jnp.int32)
    qprobs_buf = jnp.zeros((B, gamma_max, V), jnp.float32)
    ent_buf = jnp.zeros((B, gamma_max), jnp.float32)
    sig_buf = jnp.zeros((B, gamma_max, SIGNAL_VECTOR_DIM), jnp.float32)
    written = jnp.zeros((B, gamma_max), jnp.int32)

    stop0, ent0, sv0 = eval_stop(0, sig_probs0, jnp.zeros((B,), jnp.float32))
    if force_stop is not None:
        stop0 = stop0 | force_stop
    tokens_buf = tokens_buf.at[:, 0].set(tok0)
    qprobs_buf = qprobs_buf.at[:, 0].set(probs0)
    ent_buf = ent_buf.at[:, 0].set(ent0)
    sig_buf = sig_buf.at[:, 0].set(sv0)
    written = written.at[:, 0].set(1)

    def cond(state):
        i, _, _, _, _, stopped, _, _, _, _, _ = state
        return (i < gamma_max) & ~jnp.all(stopped)

    def body(state):
        i, tok, prev_ent, tbuf, qbuf, stopped, ebuf, sbuf, wrt, cache, rng = state
        logits, cache = step_fn(tok[:, None], cache)
        rng, k = split_fn(rng)
        probs = _probs(logits[:, -1], temperature)
        sig_probs = _probs(logits[:, -1], 1.0)
        nxt = sample_fn(logits[:, -1], k)
        stop_i, ent_i, sv_i = eval_stop(i, sig_probs, prev_ent)
        tbuf = tbuf.at[:, i].set(jnp.where(stopped, tbuf[:, i], nxt))
        qbuf = qbuf.at[:, i].set(jnp.where(stopped[:, None], qbuf[:, i], probs))
        ebuf = ebuf.at[:, i].set(jnp.where(stopped, ebuf[:, i], ent_i))
        sbuf = sbuf.at[:, i].set(jnp.where(stopped[:, None], sbuf[:, i], sv_i))
        wrt = wrt.at[:, i].set(jnp.where(stopped, wrt[:, i], 1))
        stopped = stopped | stop_i
        return (i + 1, nxt, ent_i, tbuf, qbuf, stopped, ebuf, sbuf, wrt, cache, rng)

    state = (jnp.int32(1), tok0, ent0, tokens_buf, qprobs_buf, stop0,
             ent_buf, sig_buf, written, cache, rng)
    _, _, _, tbuf, qbuf, _, ebuf, sbuf, wrt, cache, _ = jax.lax.while_loop(
        cond, body, state)

    n_drafted = jnp.sum(wrt, axis=1)
    return DraftResult(tbuf, n_drafted, qbuf, cache, ebuf, sbuf)


def _signals_with_diff_fix(sig_probs, prev_ent, lam, i):
    """Per-token signal dict; SVIP-Difference needs a previous step, so the
    diff is defined as 0 at i == 0."""
    sig = signals_from_probs(sig_probs, prev_ent, lam, i)
    sig["prev_sqrt_entropy"] = jnp.where(
        i == 0, sig["sqrt_entropy"], sig["prev_sqrt_entropy"])
    return sig


def _draft_core(params, cfg, spec: CacheSpec, cache, in_tokens, arm_per_pos,
                lam, rng, *, arms: Tuple[Arm, ...], gamma_max: int,
                temperature: float = 0.0):
    """Single-stream drafting core (traced; see ``draft_session`` for the
    jitted wrapper and ``draft_session_batched`` for the vmapped one).
    Arm dispatch is ``lax.switch`` on the (shared) per-position arm index."""
    arm_fns = tuple(a.fn for a in arms)

    def eval_stop(i, sig_probs, prev_ent):
        sig = _signals_with_diff_fix(sig_probs, prev_ent, lam, i)
        per_arm = jax.lax.switch(arm_per_pos[i],
                                 [lambda s=s: s(sig) for s in arm_fns])
        return per_arm, sig["sqrt_entropy"], signal_vector(sig)

    return _run_draft_loop(
        lambda toks, c: T.step(params, cfg, toks, c, spec),
        eval_stop,
        lambda r: tuple(jax.random.split(r)),
        lambda lg, k: _sample(lg, k, temperature),
        cache, in_tokens, rng, B=in_tokens.shape[0], V=cfg.vocab_size,
        gamma_max=gamma_max, temperature=temperature)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "spec", "gamma_max", "temperature", "arms",
                     "n_prompt_tokens"))
def draft_session(params, cfg, spec: CacheSpec, cache, in_tokens, arm_per_pos,
                  lam, rng, *, arms: Tuple[Arm, ...], gamma_max: int,
                  temperature: float = 0.0, n_prompt_tokens: int = 2):
    """Draft up to gamma_max tokens with bandit-selected dynamic stopping.

    in_tokens: (B, n_prompt_tokens) — the last token(s) of the accepted
      sequence (2 for pointer-rollback caches, 1 for recompute caches).
    arm_per_pos: (gamma_max,) int32 — arm index per draft position
      (sequence-level bandits broadcast one arm; token-level vary).
    lam: AdaEDL online threshold (scalar, host-updated between sessions).
    """
    return _draft_core(params, cfg, spec, cache, in_tokens, arm_per_pos, lam,
                       rng, arms=arms, gamma_max=gamma_max,
                       temperature=temperature)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "spec", "gamma_max", "temperature", "arms",
                     "n_prompt_tokens"))
def draft_session_batched(params, cfg, spec: CacheSpec, caches, in_tokens,
                          arm_mat, lam, rngs, active, *,
                          arms: Tuple[Arm, ...], gamma_max: int,
                          temperature: float = 0.0, n_prompt_tokens: int = 2):
    """One jitted program drafting for B independent streams.

    caches: pytree of per-stream caches stacked on a leading stream axis
      (each lane is a B=1 cache, so per-stream ``pos`` comes for free).
    in_tokens: (B, n_prompt_tokens); arm_mat: (B, gamma_max) PER-STREAM arm
      indices; rngs: (B, 2) per-stream PRNG keys; active: (B,) bool mask —
      outputs of inactive lanes are zeroed (n_drafted == 0).
    Returns DraftResult with tokens (B, gamma_max) padded to gamma_max.
    """
    in_tokens, arm_mat, rngs, active = _lane_constrain(in_tokens, arm_mat,
                                                       rngs, active)

    def lane(cache, toks, arm_row, rng):
        r = _draft_core(params, cfg, spec, cache, toks[None, :], arm_row,
                        lam, rng, arms=arms, gamma_max=gamma_max,
                        temperature=temperature)
        return DraftResult(r.tokens[0], r.n_drafted[0], r.qprobs[0], r.cache,
                           r.entropies[0], r.signals[0])

    r = jax.vmap(lane)(caches, in_tokens, arm_mat, rngs)
    n_drafted = jnp.where(active, r.n_drafted, 0)
    tokens = jnp.where(active[:, None], r.tokens, 0)
    tokens, n_drafted, qprobs, ent, sig = _lane_constrain(
        tokens, n_drafted, r.qprobs, r.entropies, r.signals)
    return DraftResult(tokens, n_drafted, qprobs, r.cache, ent, sig)


def _split_rows(rngs):
    """(B, 2) keys -> (next (B, 2), use (B, 2))."""
    ks = jax.vmap(jax.random.split)(rngs)
    return ks[:, 0], ks[:, 1]


def _sample_rows(logits, rngs, temperature: float):
    """Per-row sampling with per-row keys (matches the vmapped lanes)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.vmap(lambda lg, k: jax.random.categorical(
        k, lg / temperature, axis=-1))(logits, rngs).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "spec", "gamma_max", "temperature", "arms",
                     "n_prompt_tokens"))
def draft_session_paged(params, cfg, spec, cache, in_tokens, arm_mat, lam,
                        rngs, active, *, arms: Tuple[Arm, ...],
                        gamma_max: int, temperature: float = 0.0,
                        n_prompt_tokens: int = 2):
    """Batch-native drafting over the paged cache (see module docstring).

    cache: paged cache pytree ({"lengths", "tables", "layers"}); in_tokens:
    (B, n_prompt_tokens); arm_mat: (B, gamma_max); rngs: (B, 2); active:
    (B,) bool.  Semantics match ``draft_session_batched`` lane for lane:
    inactive rows leave with n_drafted == 0 and zeroed tokens.  Same loop
    body as the dense core (``_run_draft_loop``); per-stream arms evaluate
    every arm on the batch and select per row (what vmap-of-``lax.switch``
    lowers to anyway), sampling uses per-row PRNG keys.
    """
    B = in_tokens.shape[0]
    in_tokens, arm_mat, rngs, active = _lane_constrain(in_tokens, arm_mat,
                                                       rngs, active)
    arm_fns = tuple(a.fn for a in arms)
    rows = jnp.arange(B)

    def eval_stop(i, sig_probs, prev_ent):
        sig = _signals_with_diff_fix(sig_probs, prev_ent, lam, i)
        per_arm = jnp.stack([fn(sig) for fn in arm_fns])       # (A, B)
        arm_i = jax.lax.dynamic_index_in_dim(arm_mat, i, 1, keepdims=False)
        return per_arm[arm_i, rows], sig["sqrt_entropy"], signal_vector(sig)

    r = _run_draft_loop(
        lambda toks, c: T.paged_step(params, cfg, toks, c, spec),
        eval_stop,
        _split_rows,
        lambda lg, k: _sample_rows(lg, k, temperature),
        cache, in_tokens, rngs, B=B, V=cfg.vocab_size, gamma_max=gamma_max,
        temperature=temperature,
        force_stop=~active)               # masked lanes never draft on

    n_drafted = jnp.where(active, r.n_drafted, 0)
    tokens = jnp.where(active[:, None], r.tokens, 0)
    tokens, n_drafted, qprobs, ent, sig = _lane_constrain(
        tokens, n_drafted, r.qprobs, r.entropies, r.signals)
    return DraftResult(tokens, n_drafted, qprobs, r.cache, ent, sig)


# ------------------------------------------------------------------ verify

def _accept_and_outputs(logits, drafted, n_drafted, qprobs, rng, *,
                        gamma_max: int, temperature: float, greedy: bool,
                        split_fn, uniform_fn, categorical_fn):
    """THE chain accept-loop, shared by the dense and paged verifiers.

    logits (B, gamma+1, V) from the ``[last_token] + drafted`` feed —
    logits[:, j] is the target dist for drafted[:, j].  Greedy mode accepts
    while draft == target argmax; stochastic mode is exact speculative
    sampling — accept with prob min(1, p/q), resample the first rejection
    from norm(max(p - q, 0)).  PRNG handling is injected: the dense path
    splits one key, the paged path per-row key vectors — draw ORDER is
    identical so each flavor's stream is reproducible.

      split_fn(rng) -> (rng, key); uniform_fn(key) -> (B, gamma_max) in
      [0,1); categorical_fn(dist (B, V), key) -> (B,) int32 samples.

    Returns (m, out) — accepted length and the (B, gamma_max+1) output
    buffer holding accepted tokens + the replacement/bonus token at m.
    """
    B = drafted.shape[0]
    pprobs = _probs(logits, temperature)                        # (B, g+1, V)

    idx = jnp.arange(gamma_max)
    in_draft = idx[None, :] < n_drafted[:, None]                # (B, gamma)
    p_of_draft = jnp.take_along_axis(
        pprobs[:, :gamma_max], drafted[..., None], axis=-1)[..., 0]
    q_of_draft = jnp.take_along_axis(
        qprobs, drafted[..., None], axis=-1)[..., 0]

    if greedy:
        tgt_argmax = jnp.argmax(logits[:, :gamma_max], axis=-1).astype(jnp.int32)
        accept = (drafted == tgt_argmax) & in_draft
    else:
        rng, k_acc = split_fn(rng)
        u = uniform_fn(k_acc)
        ratio = p_of_draft / jnp.maximum(q_of_draft, 1e-20)
        accept = (u < jnp.minimum(ratio, 1.0)) & in_draft

    # m = accepted prefix length
    acc_prefix = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    m = jnp.sum(acc_prefix, axis=1)                             # (B,)

    # replacement token at position m: residual distribution if m < n_drafted,
    # otherwise the bonus token straight from the target dist.
    p_at_m = jnp.take_along_axis(pprobs, m[:, None, None], axis=1)[:, 0]  # (B,V)
    q_at_m = jnp.take_along_axis(
        jnp.concatenate([qprobs, jnp.zeros((B, 1, qprobs.shape[-1]))], axis=1),
        m[:, None, None], axis=1)[:, 0]
    rejected_inside = m < n_drafted
    if greedy:
        repl = jnp.argmax(p_at_m, axis=-1).astype(jnp.int32)
    else:
        resid = jnp.maximum(p_at_m - q_at_m, 0.0)
        resid_sum = resid.sum(-1, keepdims=True)
        resid = jnp.where(resid_sum > 1e-20, resid / jnp.maximum(resid_sum, 1e-20), p_at_m)
        dist = jnp.where(rejected_inside[:, None], resid, p_at_m)
        rng, k_r = split_fn(rng)
        repl = categorical_fn(dist, k_r)

    out = jnp.where(idx[None, :] < m[:, None], drafted, 0)
    out = jnp.concatenate([out, jnp.zeros((B, 1), jnp.int32)], axis=1)
    out = out.at[jnp.arange(B), m].set(repl)
    return m, out


def _verify_core(params, cfg, spec: CacheSpec, cache, last_token, drafted,
                 n_drafted, qprobs, rng, *, gamma_max: int,
                 temperature: float = 0.0, greedy: bool = True):
    """Single-stream verification core (traced; see ``verify_session``)."""
    B = last_token.shape[0]
    inp = jnp.concatenate([last_token, drafted], axis=1)       # (B, gamma+1)
    logits, cache = T.step(params, cfg, inp, cache, spec, all_logits=True)
    with jax.named_scope("accept"):
        m, out = _accept_and_outputs(
            logits, drafted, n_drafted, qprobs, rng,
            gamma_max=gamma_max, temperature=temperature, greedy=greedy,
            split_fn=lambda r: tuple(jax.random.split(r)),
            uniform_fn=lambda k: jax.random.uniform(k, (B, gamma_max)),
            categorical_fn=lambda d, k: jax.random.categorical(
                k, jnp.log(jnp.maximum(d, 1e-30))).astype(jnp.int32))
    return VerifyResult(m, out, m + 1, cache)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "spec", "gamma_max", "temperature", "greedy"))
def verify_session(params, cfg, spec: CacheSpec, cache, last_token, drafted,
                   n_drafted, qprobs, rng, *, gamma_max: int,
                   temperature: float = 0.0, greedy: bool = True):
    """Verify drafted tokens with the target model in one forward pass.

    last_token: (B, 1) final accepted token (not yet fed to target).
    drafted: (B, gamma_max); n_drafted: (B,); qprobs: (B, gamma_max, V).

    Greedy mode: accept while draft token == target argmax. Stochastic mode:
    exact speculative sampling — accept with prob min(1, p/q), resample the
    first rejection from norm(max(p-q, 0)) so the output distribution equals
    the target model's.
    """
    return _verify_core(params, cfg, spec, cache, last_token, drafted,
                        n_drafted, qprobs, rng, gamma_max=gamma_max,
                        temperature=temperature, greedy=greedy)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "spec", "gamma_max", "temperature", "greedy"))
def verify_session_batched(params, cfg, spec: CacheSpec, caches, last_tokens,
                           drafted, n_drafted, qprobs, rngs, active, *,
                           gamma_max: int, temperature: float = 0.0,
                           greedy: bool = True):
    """One jitted program verifying B independent streams.

    caches: stacked per-stream target caches (leading stream axis);
    last_tokens: (B, 1); drafted: (B, gamma_max); n_drafted: (B,);
    qprobs: (B, gamma_max, V); rngs: (B, 2); active: (B,) bool.
    Inactive lanes come in with n_drafted == 0 and leave with
    n_accepted == n_out == 0 and zeroed out_tokens.
    """
    last_tokens, drafted, n_drafted, qprobs, rngs, active = _lane_constrain(
        last_tokens, drafted, n_drafted, qprobs, rngs, active)

    def lane(cache, last, drf, nd, qp, rng):
        r = _verify_core(params, cfg, spec, cache, last[None, :], drf[None],
                         nd[None], qp[None], rng, gamma_max=gamma_max,
                         temperature=temperature, greedy=greedy)
        return VerifyResult(r.n_accepted[0], r.out_tokens[0], r.n_out[0],
                            r.cache)

    r = jax.vmap(lane)(caches, last_tokens, drafted, n_drafted, qprobs, rngs)
    m = jnp.where(active, r.n_accepted, 0)
    out = jnp.where(active[:, None], r.out_tokens, 0)
    m, out, n_out = _lane_constrain(m, out, jnp.where(active, r.n_out, 0))
    return VerifyResult(m, out, n_out, r.cache)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "spec", "gamma_max", "temperature", "greedy"))
def verify_session_paged(params, cfg, spec, cache, last_tokens, drafted,
                         n_drafted, qprobs, rngs, active, *, gamma_max: int,
                         temperature: float = 0.0, greedy: bool = True):
    """Batch-native verification over the paged cache.

    One ``paged_step`` forward serves every stream at its own position;
    the accept-loop is the SAME ``_accept_and_outputs`` body as the dense
    verifier, with per-row PRNG keys injected.  Inactive lanes (n_drafted
    == 0) leave with zeroed outputs; their cache writes land in the trash
    block.
    """
    last_tokens, drafted, n_drafted, qprobs, rngs, active = _lane_constrain(
        last_tokens, drafted, n_drafted, qprobs, rngs, active)
    inp = jnp.concatenate([last_tokens, drafted], axis=1)       # (B, g+1)
    logits, cache = T.paged_step(params, cfg, inp, cache, spec, all_logits=True)
    with jax.named_scope("accept"):
        m, out = _accept_and_outputs(
            logits, drafted, n_drafted, qprobs, rngs,
            gamma_max=gamma_max, temperature=temperature, greedy=greedy,
            split_fn=_split_rows,
            uniform_fn=jax.vmap(lambda k: jax.random.uniform(k, (gamma_max,))),
            categorical_fn=lambda d, k: jax.vmap(
                lambda d1, k1: jax.random.categorical(
                    k1, jnp.log(jnp.maximum(d1, 1e-30))))(d, k).astype(jnp.int32))
    m = jnp.where(active, m, 0)
    out = jnp.where(active[:, None], out, 0)
    m, out, n_out = _lane_constrain(m, out, jnp.where(active, m + 1, 0))
    return VerifyResult(m, out, n_out, cache)


# ----------------------------------------------------------- chunk prefill

CHUNK_PREFILL_STATICS = ("cfg", "spec")


@functools.partial(jax.jit, static_argnames=CHUNK_PREFILL_STATICS)
def chunk_prefill_paged(params, cfg, spec, lane, tokens, n_valid):
    """Resumable chunk-prefill session over ONE paged lane view.

    Feeds a ``(1, C)`` token buffer whose first ``n_valid`` entries are
    real prompt tokens; any pad tail rides through the forward (causal
    attention keeps it invisible to the real tokens, and its pool writes
    land at positions the rollback marks dead inside the stream's own
    reserved pages) and is erased by an O(1) ``paged_rollback`` to
    ``start + n_valid``.  Position state lives entirely in the lane's
    ``lengths`` vector, so the program RESUMES AT ARBITRARY OFFSETS: a
    scheduler can interleave one bounded chunk per serving tick instead of
    stalling a tick on a full-prompt prefill, and every chunk of every
    prompt reuses one compiled shape per chunk width.  With ``n_valid ==
    C`` (no pads) the rollback is the identity length write, which is how
    the engines keep chunked and monolithic prefill BIT-IDENTICAL: both
    feed the same chunk schedule through this one program.
    """
    start = lane["lengths"]
    with jax.named_scope("prefill"):
        _, lane = T.paged_step(params, cfg, tokens, lane, spec)
    return paged_rollback(lane, start + jnp.asarray(n_valid, jnp.int32))


# ------------------------------------------------------------- sharded jits

def fresh_session_jits(*, paged: bool = False):
    """Per-engine re-jits of the single-stream (or paged batch-native)
    session primitives, with the same static argnames as the module-level
    ones.

    A mesh-aware engine must NOT share the module-level jits: the models'
    ``constrain`` annotations resolve against the mesh active at TRACE
    time, and a jit's trace cache is keyed on avals only — so one engine's
    meshless trace would be silently reused for another engine's sharded
    call (or a mesh-bound trace would poison a single-device engine).
    Giving each mesh-bound engine fresh jit objects keeps trace caches
    per-placement.
    """
    d = draft_session_paged if paged else draft_session
    v = verify_session_paged if paged else verify_session
    return (jax.jit(d.__wrapped__, static_argnames=DRAFT_STATICS),
            jax.jit(v.__wrapped__, static_argnames=VERIFY_STATICS))


def lane_sharding(mesh, shape) -> NamedSharding:
    """NamedSharding placing the leading stream-lane axis of ``shape`` on
    the ("pod","data") batch axes (indivisible axes drop per
    ``resolve_spec``, so B=1 / odd-B shapes degrade to replicated)."""
    return NamedSharding(mesh, resolve_spec(mesh, (BATCH_AXES,), shape))


def make_sharded_sessions(mesh, *, cfg_d, cfg_t, dspec, tspec, dparams_sh,
                          tparams_sh, dcache_sh, tcache_sh, batch_size: int,
                          gamma_max: int, arms: Tuple[Arm, ...],
                          temperature: float, greedy: bool,
                          n_prompt_tokens: int, paged: bool = False):
    """Jit the batched (or paged batch-native) draft/verify programs with
    explicit ``NamedSharding`` in/out shardings for one engine's
    (B, gamma_max) deployment on ``mesh``.

    Slot lanes — tokens, arm rows, PRNG keys, active masks, and every
    per-lane output — shard over the ("pod","data") batch axes; params and
    caches use the pytree shardings the engine placed them with
    (``launch/shardings.py``), so the compiled program never re-lays-out
    its resident state.  Returns ``(draft_fn, verify_fn)`` with the
    signatures of the module-level primitives minus the static arguments
    (closed over here).
    """
    B, g = batch_size, gamma_max
    rep = NamedSharding(mesh, P())
    lane = functools.partial(lane_sharding, mesh)
    draft_raw = (draft_session_paged if paged else
                 draft_session_batched).__wrapped__
    verify_raw = (verify_session_paged if paged else
                  verify_session_batched).__wrapped__

    def draft_fn(params, caches, in_tokens, arm_mat, lam, rngs, active):
        return draft_raw(params, cfg_d, dspec, caches, in_tokens, arm_mat,
                         lam, rngs, active, arms=arms, gamma_max=g,
                         temperature=temperature,
                         n_prompt_tokens=n_prompt_tokens)

    def verify_fn(params, caches, last_tokens, drafted, n_drafted, qprobs,
                  rngs, active):
        return verify_raw(params, cfg_t, tspec, caches, last_tokens, drafted,
                          n_drafted, qprobs, rngs, active, gamma_max=g,
                          temperature=temperature, greedy=greedy)

    V = cfg_d.vocab_size
    draft_jit = jax.jit(
        draft_fn,
        in_shardings=(dparams_sh, dcache_sh, lane((B, n_prompt_tokens)),
                      lane((B, g)), rep, lane((B, 2)), lane((B,))),
        out_shardings=DraftResult(
            lane((B, g)), lane((B,)), lane((B, g, V)), dcache_sh,
            lane((B, g)), lane((B, g, SIGNAL_VECTOR_DIM))))
    verify_jit = jax.jit(
        verify_fn,
        in_shardings=(tparams_sh, tcache_sh, lane((B, 1)), lane((B, g)),
                      lane((B,)), lane((B, g, V)), lane((B, 2)),
                      lane((B,))),
        out_shardings=VerifyResult(
            lane((B,)), lane((B, g + 1)), lane((B,)), tcache_sh))
    return draft_jit, verify_jit


# ------------------------------------------------------------- fused tick

class FusedTick(NamedTuple):
    """Device-resident outcome buffer of one fused serving tick.

    The host reads the integer/trace fields ONE STEP BEHIND (the engine's
    launch/flush split); the rolled-back caches feed the next tick without
    ever leaving the device."""
    n_drafted: jnp.ndarray     # (B,) int32
    n_accepted: jnp.ndarray    # (B,) int32
    out_tokens: jnp.ndarray    # (B, gamma_max+1) accepted + replacement/bonus
    entropies: jnp.ndarray     # (B, gamma_max) per-position sqrt-entropy
    signals: jnp.ndarray       # (B, gamma_max, 6) per-position signal vector
    dcache: dict               # draft cache AFTER output-side rollback
    tcache: dict               # target cache AFTER output-side rollback


FUSED_STATICS = ("cfg_d", "cfg_t", "dspec", "tspec", "arms", "gamma_max",
                 "temperature", "greedy", "n_prompt_tokens", "paged")


def _fused_tick_core(dparams, tparams, cfg_d, cfg_t, dspec: CacheSpec,
                     tspec: CacheSpec, dcaches, tcaches, in_tokens,
                     last_tokens, arm_mat, lam, drngs, vrngs, active,
                     lengths, dkeep, tkeep, *, arms: Tuple[Arm, ...],
                     gamma_max: int, temperature: float, greedy: bool,
                     n_prompt_tokens: int, paged: bool):
    """ONE device program per serving tick: input-side rollback -> draft
    while-loop -> verify forward -> accept -> output-side rollback.  Each
    stage runs under a ``jax.named_scope`` (``rollback``, ``draft``,
    ``verify``, and ``accept`` inside ``verify``), so a profiler trace
    names the device ops of each stage (docs/serving.md, "Tracing a
    server").

    Calls the exact traced bodies of the synchronous primitives
    (``draft_session_batched`` / ``verify_session_batched`` or their paged
    twins), so per-lane arithmetic — and therefore every (n_drafted,
    n_accepted, out_tokens) outcome the bandit consumes — is the same
    computation the two-dispatch path runs; only the host round-trips
    between the stages disappear.

    lengths: (B,) int32 per-lane sequence lengths (len(seq));
    dkeep/tkeep: (B,) int32 cache pointers (dense) or lengths (paged) to
    KEEP for inactive lanes — the on-device analog of the engine's host
    mirrors.  Requires cheap-rollback caches on both models (the engine
    gates fusion on ``CacheSpec.cheap_rollback``)."""
    lengths, dkeep, tkeep = _lane_constrain(lengths, dkeep, tkeep)
    rb = paged_rollback if paged else rollback
    draft_raw = (draft_session_paged if paged else
                 draft_session_batched).__wrapped__
    verify_raw = (verify_session_paged if paged else
                  verify_session_batched).__wrapped__

    # input-side rollback: re-feed the last two accepted tokens
    with jax.named_scope("rollback"):
        dcaches_in = rb(dcaches, jnp.where(active, lengths - 2, dkeep))
    with jax.named_scope("draft"):
        dres = draft_raw(dparams, cfg_d, dspec, dcaches_in, in_tokens,
                         arm_mat, lam, drngs, active, arms=arms,
                         gamma_max=gamma_max, temperature=temperature,
                         n_prompt_tokens=n_prompt_tokens)
    with jax.named_scope("verify"):
        vres = verify_raw(tparams, cfg_t, tspec, tcaches, last_tokens,
                          dres.tokens, dres.n_drafted, dres.qprobs, vrngs,
                          active, gamma_max=gamma_max,
                          temperature=temperature, greedy=greedy)
    m = vres.n_accepted
    # output-side rollback (cache invariant: pos/length == len(seq) - 1 fed)
    with jax.named_scope("rollback"):
        tcache = rb(vres.cache, jnp.where(active, lengths + m, tkeep))
        dcache = rb(dres.cache, jnp.where(active, lengths + m - 1, dkeep))
    return FusedTick(dres.n_drafted, m, vres.out_tokens, dres.entropies,
                     dres.signals, dcache, tcache)


@functools.partial(jax.jit, static_argnames=FUSED_STATICS)
def fused_session_tick(dparams, tparams, cfg_d, cfg_t, dspec, tspec,
                       dcaches, tcaches, in_tokens, last_tokens, arm_mat,
                       lam, drngs, vrngs, active, lengths, dkeep, tkeep, *,
                       arms: Tuple[Arm, ...], gamma_max: int,
                       temperature: float = 0.0, greedy: bool = True,
                       n_prompt_tokens: int = 2, paged: bool = False):
    """Jitted fused serving tick (see ``_fused_tick_core``)."""
    return _fused_tick_core(dparams, tparams, cfg_d, cfg_t, dspec, tspec,
                            dcaches, tcaches, in_tokens, last_tokens,
                            arm_mat, lam, drngs, vrngs, active, lengths,
                            dkeep, tkeep, arms=arms, gamma_max=gamma_max,
                            temperature=temperature, greedy=greedy,
                            n_prompt_tokens=n_prompt_tokens, paged=paged)


def fresh_fused_jit():
    """Per-engine re-jit of ``fused_session_tick`` (same trace-cache
    hygiene as ``fresh_session_jits``)."""
    return jax.jit(fused_session_tick.__wrapped__,
                   static_argnames=FUSED_STATICS)


def make_sharded_fused(mesh, *, cfg_d, cfg_t, dspec, tspec, dparams_sh,
                       tparams_sh, dcache_sh, tcache_sh, batch_size: int,
                       gamma_max: int, arms: Tuple[Arm, ...],
                       temperature: float, greedy: bool,
                       n_prompt_tokens: int, paged: bool = False):
    """Jit the fused tick with explicit in/out shardings for one engine's
    deployment on ``mesh`` (``launch/shardings.fused_tick_shardings``):
    per-lane operands — tokens, arm rows, PRNG keys, the ragged length /
    keep-pointer vectors — shard over the ("pod","data") batch axes, params
    and caches keep their resident pytree shardings."""
    from repro.launch.shardings import fused_tick_shardings
    ins, outs = fused_tick_shardings(
        mesh, batch_size=batch_size, gamma_max=gamma_max,
        n_prompt_tokens=n_prompt_tokens, signal_dim=SIGNAL_VECTOR_DIM,
        dparams_sh=dparams_sh, tparams_sh=tparams_sh,
        dcache_sh=dcache_sh, tcache_sh=tcache_sh)

    def tick_fn(dparams, tparams, dcaches, tcaches, in_tokens, last_tokens,
                arm_mat, lam, drngs, vrngs, active, lengths, dkeep, tkeep):
        return _fused_tick_core(
            dparams, tparams, cfg_d, cfg_t, dspec, tspec, dcaches, tcaches,
            in_tokens, last_tokens, arm_mat, lam, drngs, vrngs, active,
            lengths, dkeep, tkeep, arms=arms, gamma_max=gamma_max,
            temperature=temperature, greedy=greedy,
            n_prompt_tokens=n_prompt_tokens, paged=paged)

    return jax.jit(tick_fn, in_shardings=ins,
                   out_shardings=FusedTick(**outs))
