"""Host-driven speculative-decoding generation engine.

Runs the draft/verify session loop around the jitted primitives in
``spec_decode.py``, maintains the cache invariants for both rollback
strategies (pointer rollback for attention/MLA caches, snapshot+recompute
for recurrent state), and reports the paper's metrics: accepted length m,
acceptance rate %, and speedup s (wall-clock and an analytic cost model —
CPU wall-clock is not TPU wall-clock, DESIGN.md §6).
"""
from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import transformer as T
from repro.models.cache import (POOL_LEAF_KEYS, BlockAllocator,
                                EncoderSegmentPool, PoolExhausted,
                                PrefixCache, paged_copy_block, paged_rollback,
                                rollback)
from repro.models.quant import quantize_params
from repro.models.sharding import use_mesh
from .controller import Controller, TapOutTreeSequence
from .rewards import (modeled_session_cost, moe_routed_frac,
                      precision_cost_factor)
from .spec_decode import (_probs, chunk_prefill_paged, draft_session,
                          draft_session_batched, draft_session_paged,
                          fresh_session_jits, fused_session_tick,
                          make_sharded_fused, make_sharded_sessions,
                          verify_session, verify_session_batched,
                          verify_session_paged)
from .tree import TreeSpec, verify_walk


def _on_mesh(fn):
    """Run an engine method with the engine's mesh active, so every program
    traced inside it resolves its ``constrain`` annotations against that
    mesh (a no-op for meshless engines)."""
    @functools.wraps(fn)
    def inner(self, *args, **kwargs):
        with self._mesh_ctx():
            return fn(self, *args, **kwargs)
    return inner


class _ShardingMixin:
    """Device-placement plumbing shared by every engine.

    ``mesh=None`` (the default) leaves everything exactly as before: one
    device, module-level jitted primitives, no placement.  With a mesh the
    engine places its params (serve-mode rules: weights resident, "model"
    tensor-parallel only — see ``launch/shardings.py``) and its caches at
    init, and every computation downstream of those committed arrays runs
    on the mesh's device set.  The bandit controller needs none of this:
    it is host-side O(arms) state fed by order-independent observation
    merges, so the SAME controller code serves 1 device or 512.
    """

    mesh = None
    backend_name = "single"

    def describe(self) -> dict:
        """Canonical description of this engine's deployment settings —
        the single schema benchmarks and ``SpecServer.throughput_stats``
        attach to every row they emit (docs/serving.md)."""
        d = {
            "backend": self.backend_name,
            "batch_size": int(getattr(self, "batch_size", 1)),
            "max_len": int(self.max_len),
            "gamma_max": int(self.gamma_max),
            "temperature": float(self.temperature),
            "greedy": bool(self.greedy),
            "kv_dtype": self.kv_dtype or "fp",
            "fused": bool(getattr(self, "fused", False)),
            "devices": (int(self.mesh.devices.size)
                        if self.mesh is not None else 1),
            "mesh_axes": ({k: int(v) for k, v in self.mesh.shape.items()}
                          if self.mesh is not None else None),
        }
        d["drafter"] = self._drafter_blob()
        rf = float(getattr(self, "_routed_frac", 0.0))
        if rf > 0.0:
            n = int(getattr(self, "_moe_sessions", 0))
            m = self.target.cfg.moe
            d["moe"] = {
                "routed_frac": rf,
                "top_k": int(m.top_k),
                "num_experts": int(m.num_experts),
                "sessions": n,
                "mean_routing_density": (float(self._moe_density_sum / n)
                                         if n else 1.0),
            }
        return d

    def _init_moe_accounting(self):
        """Routed-cost accounting state for MoE targets: ``_routed_frac``
        is the share of the target's active per-token parameters that are
        routed experts (0 for dense targets — every read is gated on it),
        the density sum/count feed ``describe()["moe"]``."""
        self._routed_frac = moe_routed_frac(self.target.cfg)
        self._moe_density_sum = 0.0
        self._moe_sessions = 0

    def _routing_density_rows(self, tcache) -> np.ndarray:
        """Per-lane routing density of the verify chunk just fed: the
        cache's ``moe_stats`` channel (mean distinct experts hit per routed
        layer) over ``top_k``.  One decode token gives exactly 1.0; a
        gamma-token verify PHYSICALLY streams up to gamma * top_k distinct
        experts' weights, so density > 1 raises the routed share of the
        modeled verify cost (``rewards.modeled_session_cost``) — the
        workload axis the bandit's cost-adjusted reward learns from."""
        k = max(int(self.target.cfg.moe.top_k), 1)
        return np.asarray(tcache["moe_stats"], np.float64) / k

    def _drafter_blob(self) -> dict:
        """Drafter identity, stamped into every describe()/bench row: which
        model drafts (name + kind) and — when the engine serves a
        heterogeneous ``DrafterPool`` — the full pool (names, kinds,
        relative costs, per-stream state bytes)."""
        cfg = self.draft.cfg
        blob = {"name": cfg.name,
                "kind": "ssd" if cfg.is_attention_free else "kv",
                "pool": None}
        pool = getattr(self, "drafters", None)
        if pool is not None:
            blob["name"] = pool.default
            blob["kind"] = pool.kind(pool.default)
            blob["pool"] = pool.describe(int(self.max_len),
                                         kv_dtype=self.kv_dtype)
        return blob

    def _mesh_ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        return use_mesh(self.mesh)

    def _meshless_fused(self, *, paged: bool, draft: "ModelBundle" = None,
                        dspec=None):
        """Bind this engine's statics onto the module-level fused-tick jit
        (meshless engines share its trace cache, exactly like the
        synchronous session primitives).  ``draft``/``dspec`` override the
        draft-side statics for drafter-pool engines: each drafter gets its
        own entry in the SAME module-level trace cache, so switching
        drafters between ticks after warmup never re-traces."""
        draft = draft or self.draft
        statics = dict(cfg_d=draft.cfg, cfg_t=self.target.cfg,
                       dspec=dspec or self.dspec, tspec=self.tspec,
                       arms=self.controller.arms, gamma_max=self.gamma_max,
                       temperature=self.temperature, greedy=self.greedy,
                       n_prompt_tokens=2, paged=paged)

        def tick(dparams, tparams, dcaches, tcaches, in_tokens, last_tokens,
                 arm_mat, lam, drngs, vrngs, active, lengths, dkeep, tkeep):
            return fused_session_tick(
                dparams, tparams, dcaches=dcaches, tcaches=tcaches,
                in_tokens=in_tokens, last_tokens=last_tokens,
                arm_mat=arm_mat, lam=lam, drngs=drngs, vrngs=vrngs,
                active=active, lengths=lengths, dkeep=dkeep, tkeep=tkeep,
                **statics)
        return tick

    def _place_bundles(self):
        """Shard draft/target params over the mesh (serve-mode rules);
        keeps the sharding pytrees for the session programs' in_shardings."""
        self._dparams_sh = self._tparams_sh = None
        if self.mesh is None:
            return
        from repro.launch.shardings import params_shardings
        self._dparams_sh = params_shardings(self.mesh, self.draft.params,
                                            mode="serve")
        self._tparams_sh = params_shardings(self.mesh, self.target.params,
                                            mode="serve")
        self.draft = ModelBundle(
            jax.device_put(self.draft.params, self._dparams_sh),
            self.draft.cfg, cost_per_token=self.draft.cost_per_token)
        self.target = ModelBundle(
            jax.device_put(self.target.params, self._tparams_sh),
            self.target.cfg, cost_per_token=self.target.cost_per_token)

    def _place_variant(self, bundle: "ModelBundle") -> "ModelBundle":
        """Shard an extra weight variant (e.g. an int8 draft copy)."""
        if self.mesh is None:
            return bundle
        from repro.launch.shardings import params_shardings
        sh = params_shardings(self.mesh, bundle.params, mode="serve")
        return ModelBundle(jax.device_put(bundle.params, sh), bundle.cfg,
                           cost_per_token=bundle.cost_per_token)

    def _place_cache(self, cache, *, paged: bool = False, slots: bool = False):
        """Place a cache pytree per the launch-layer rules (dense B=1,
        slot-stacked, or paged-pool layout).  The sharding pytree is
        memoized per layout — this runs on the serving hot path (admission,
        release, canonical re-pinning after lane writes) and an engine's
        cache structure never changes after init."""
        if self.mesh is None:
            return cache
        # treedef + leaf shapes in the key: one engine places draft AND
        # target caches (different structures/dims) through the same
        # layout flags, and resolve_spec decisions depend on shapes
        flat, treedef = jax.tree_util.tree_flatten(cache)
        key = (paged, slots, treedef, tuple(a.shape for a in flat))
        shardings = getattr(self, "_cache_sh", None)
        if shardings is None:
            shardings = self._cache_sh = {}
        if key not in shardings:
            from repro.launch.shardings import (cache_shardings,
                                                paged_cache_shardings,
                                                slot_cache_shardings)
            sh_fn = (paged_cache_shardings if paged
                     else slot_cache_shardings if slots else cache_shardings)
            shardings[key] = sh_fn(self.mesh, cache)
        return jax.device_put(cache, shardings[key])


@dataclass
class ModelBundle:
    params: dict
    cfg: object
    # relative cost of one forward token (roofline-style: active params)
    cost_per_token: float = 0.0

    def __post_init__(self):
        if not self.cost_per_token:
            self.cost_per_token = float(self.cfg.active_param_count())


def quantized_bundle(bundle: ModelBundle) -> ModelBundle:
    """An int8-weight copy of a bundle: params quantized once
    (``models/quant.py``), modeled per-token cost scaled by the int8
    precision factor (memory-bound decode streams ~half the bytes)."""
    return ModelBundle(quantize_params(bundle.params), bundle.cfg,
                       cost_per_token=bundle.cost_per_token
                       * precision_cost_factor("int8"))


@dataclass
class SessionStats:
    n_drafted: int
    n_accepted: int
    arm: int


@dataclass
class GenResult:
    tokens: List[int]
    prompt_len: int
    sessions: List[SessionStats] = field(default_factory=list)
    wall_time_s: float = 0.0
    modeled_cost: float = 0.0
    traces: List[dict] = field(default_factory=list)

    @property
    def new_tokens(self) -> int:
        return len(self.tokens) - self.prompt_len

    @property
    def total_drafted(self) -> int:
        return sum(s.n_drafted for s in self.sessions)

    @property
    def total_accepted(self) -> int:
        return sum(s.n_accepted for s in self.sessions)

    @property
    def accept_rate(self) -> float:
        d = self.total_drafted
        return self.total_accepted / d if d else 0.0

    @property
    def mean_accepted(self) -> float:
        n = len(self.sessions)
        return self.total_accepted / n if n else 0.0

    # canonical name shared with the serving/bench schema: accepted tokens
    # per verify pass (every session runs exactly one verify forward)
    accepted_per_verify = mean_accepted


class _StepMixin:
    """Shared cache-advance plumbing for the single-stream and batched
    engines (both expose .draft/.target bundles and .dspec/.tspec)."""

    def _jit_step(self, which: str, length: int, all_logits: bool = False):
        key = (which, length, all_logits)
        if key not in self._step_cache:
            bundle = self.draft if which == "draft" else self.target
            spec = self.dspec if which == "draft" else self.tspec

            @jax.jit
            def fn(params, tokens, cache):
                return T.step(params, bundle.cfg, tokens, cache, spec,
                              all_logits=all_logits)
            self._step_cache[key] = fn
        return self._step_cache[key]

    def _advance(self, which: str, params, cache, tokens: np.ndarray):
        """Feed ``tokens`` (1, L) through the model, return new cache."""
        if tokens.shape[1] == 0:
            return cache
        fn = self._jit_step(which, tokens.shape[1])
        _, cache = fn(params, jnp.asarray(tokens, jnp.int32), cache)
        return cache

    def _jit_step_for(self, tag: str, bundle: "ModelBundle", spec,
                      length: int):
        """Like ``_jit_step`` but for an arbitrary (tagged) bundle — the
        per-drafter catch-up feeds of the drafter-pool engine.  Keyed by
        (tag, length) in the same per-engine cache."""
        key = (tag, length, False)
        if key not in self._step_cache:
            @jax.jit
            def fn(params, tokens, cache):
                return T.step(params, bundle.cfg, tokens, cache, spec)
            self._step_cache[key] = fn
        return self._step_cache[key]

    def _advance_with(self, tag: str, bundle: "ModelBundle", spec, cache,
                      tokens: np.ndarray):
        """Feed ``tokens`` (1, L) through a tagged bundle's model."""
        if tokens.shape[1] == 0:
            return cache
        fn = self._jit_step_for(tag, bundle, spec, tokens.shape[1])
        _, cache = fn(bundle.params, jnp.asarray(tokens, jnp.int32), cache)
        return cache

    def jit_cache_sizes(self) -> dict:
        """Trace-cache entry counts of every program this engine's ticks
        can populate — the zero-retrace-after-warmup assertion surface
        (tests/test_drafters.py): warm the engine, snapshot, keep serving
        with drafter switches, assert unchanged."""
        def n(fn):
            try:
                return int(fn._cache_size())
            except Exception:
                return -1
        return {"fused_tick": n(fused_session_tick),
                "draft_batched": n(draft_session_batched),
                "verify_batched": n(verify_session_batched),
                "step_cache": len(self._step_cache)}


class SpecEngine(_StepMixin, _ShardingMixin):
    """Single-stream engine.  ``kv_dtype="int8"`` stores both models' KV
    caches quantized (``models/quant.py``); ``quant_draft=True`` swaps the
    draft bundle for an int8-weight copy with the precision-scaled modeled
    cost; ``mesh=`` places params and caches across devices
    (docs/sharding.md) — the batched/paged/tree engines take the same
    knobs."""

    def __init__(self, draft: ModelBundle, target: ModelBundle,
                 controller: Controller, *, max_len: int = 2048,
                 temperature: float = 0.0, greedy: bool = True,
                 cache_dtype=jnp.float32, kv_dtype: Optional[str] = None,
                 quant_draft: bool = False, seed: int = 0, mesh=None):
        if quant_draft:
            draft = quantized_bundle(draft)
        self.draft, self.target = draft, target
        self.mesh = mesh
        self._place_bundles()
        self._draft_session, self._verify_session = (
            (draft_session, verify_session) if mesh is None
            else fresh_session_jits())
        self.controller = controller
        self.gamma_max = controller.gamma_max
        self.max_len = max_len
        self.temperature = temperature
        self.greedy = greedy
        self.cache_dtype = cache_dtype
        self.kv_dtype = kv_dtype
        self.rng = jax.random.PRNGKey(seed)
        self.collect_traces = False
        self._step_cache: Dict[tuple, callable] = {}
        _, self.dspec = T.init_cache(draft.cfg, 1, max_len, cache_dtype,
                                     kv_dtype=kv_dtype)
        _, self.tspec = T.init_cache(target.cfg, 1, max_len, cache_dtype,
                                     kv_dtype=kv_dtype)
        self.draft_cheap = self.dspec.cheap_rollback
        self.target_cheap = self.tspec.cheap_rollback
        self._init_moe_accounting()

    # -------------------------------------------------------- helpers
    def _next_rng(self):
        self.rng, k = jax.random.split(self.rng)
        return k

    # -------------------------------------------------------- streams
    @_on_mesh
    def start_stream(self, prompt: List[int], *, frame_embeds=None,
                     patch_embeds=None) -> dict:
        """Prefill a new generation stream; returns the stream state.

        Conditioning (target-side only — the draft stays a text-only
        decoder, which greedy speculative decoding keeps output-exact):

          * ``frame_embeds`` (T, frontend_dim) — enc-dec targets encode it
            once and cache the per-layer cross-KV inside ``tcache`` (the
            jitted sessions thread it untouched, so nothing downstream
            changes);
          * ``patch_embeds`` (P, vit_dim) — vision targets prepend P
            projected patch positions before the prompt, so every TARGET
            cache position is offset by ``toff = P`` from ``len(seq)``;
            the session's target rollbacks carry that offset.
        """
        assert len(prompt) >= 2, "need >= 2 prompt tokens"
        seq = list(prompt)
        res = GenResult(tokens=seq, prompt_len=len(prompt))
        dcache, _ = T.init_cache(self.draft.cfg, 1, self.max_len,
                                 self.cache_dtype, kv_dtype=self.kv_dtype)
        tcache, _ = T.init_cache(self.target.cfg, 1, self.max_len,
                                 self.cache_dtype, kv_dtype=self.kv_dtype)
        dcache = self._place_cache(dcache)
        tcache = self._place_cache(tcache)
        pre = np.asarray(seq[:-1], np.int32)[None]   # invariant pos = len-1
        dcache = self._advance("draft", self.draft.params, dcache, pre)
        toff = 0
        if frame_embeds is not None or patch_embeds is not None:
            fe = pe = None
            if frame_embeds is not None:
                fe = jnp.asarray(frame_embeds)
                fe = fe[None] if fe.ndim == 2 else fe
            if patch_embeds is not None:
                pe = jnp.asarray(patch_embeds)
                pe = pe[None] if pe.ndim == 2 else pe
                toff = int(pe.shape[1])
            assert len(prompt) + self.gamma_max + 2 + toff <= self.max_len, \
                "prompt + patches cannot fit a session within max_len"
            # one conditioned prefill feed (once per stream — traced per
            # prompt shape like the plain _advance path)
            _, tcache = T.step(self.target.params, self.target.cfg,
                               jnp.asarray(pre, jnp.int32), tcache,
                               self.tspec, frame_embeds=fe, patch_embeds=pe)
        else:
            tcache = self._advance("target", self.target.params, tcache, pre)
        return {"seq": seq, "res": res, "dcache": dcache, "tcache": tcache,
                "toff": toff, "done": False}

    @_on_mesh
    def session_step(self, state: dict, eos_id: Optional[int] = None) -> dict:
        """Run ONE draft/verify session on a stream (serving-layer unit)."""
        seq, res = state["seq"], state["res"]
        dcache, tcache = state["dcache"], state["tcache"]
        toff = int(state.get("toff", 0))     # target-only position offset
        c_d = self.draft.cost_per_token
        c_t = self.target.cost_per_token
        if True:
            L = len(seq)
            arm_per_pos = self.controller.begin()
            gamma = len(arm_per_pos)

            # ---- draft
            if self.draft_cheap:
                dcache_in = rollback(dcache, L - 2)
                in_toks = jnp.asarray([seq[-2:]], jnp.int32)
                n_in = 2
            else:
                dcache_snapshot = dcache
                dcache_in = dcache
                in_toks = jnp.asarray([seq[-1:]], jnp.int32)
                n_in = 1
            dres = self._draft_session(
                self.draft.params, self.draft.cfg, self.dspec, dcache_in,
                in_toks, jnp.asarray(arm_per_pos), jnp.float32(self.controller.lam),
                self._next_rng(), arms=self.controller.arms, gamma_max=gamma,
                temperature=self.temperature, n_prompt_tokens=n_in)
            n_drafted = int(dres.n_drafted[0])

            # ---- verify
            if not self.target_cheap:
                tcache_snapshot = tcache
            vres = self._verify_session(
                self.target.params, self.target.cfg, self.tspec, tcache,
                jnp.asarray([seq[-1:]], jnp.int32)[:, 0:1], dres.tokens,
                dres.n_drafted, dres.qprobs, self._next_rng(),
                gamma_max=gamma, temperature=self.temperature,
                greedy=self.greedy)
            m = int(vres.n_accepted[0])
            out = np.asarray(vres.out_tokens[0, :m + 1]).tolist()

            # ---- cache maintenance (invariant: pos = len(seq)-1)
            accepted_feed = np.asarray([seq[-1:] + out[:-1]], np.int32)  # (1, m+1)
            seq.extend(out)
            if self.target_cheap:
                tcache = rollback(vres.cache, L + m + toff)
            else:
                tcache = self._advance("target", self.target.params,
                                       tcache_snapshot, accepted_feed)
            if self.draft_cheap:
                dcache = rollback(dres.cache, L + m - 1)
            else:
                dcache = self._advance("draft", self.draft.params,
                                       dcache_snapshot, accepted_feed)

            # ---- controller update + accounting
            self.controller.update(arm_per_pos, n_drafted, m)
            res.sessions.append(SessionStats(n_drafted, m, int(arm_per_pos[0])))
            if self.collect_traces:
                res.traces.append({
                    "signals": np.asarray(dres.signals[0]),
                    "entropies": np.asarray(dres.entropies[0]),
                    "n_drafted": n_drafted, "n_accepted": m,
                    "position_base": 0})
            density = 1.0
            if self._routed_frac > 0.0:
                density = float(self._routing_density_rows(vres.cache)[0])
                self._moe_density_sum += density
                self._moe_sessions += 1
            res.modeled_cost += modeled_session_cost(
                n_drafted + n_in - 1, c_d, c_t,
                routed_frac=self._routed_frac, routing_density=density)
            if eos_id is not None and eos_id in out:
                seq[:] = seq[:len(seq) - len(out) + out.index(eos_id) + 1]
                state["done"] = True
            if len(seq) + gamma + 2 + toff >= self.max_len:
                state["done"] = True

        state["dcache"], state["tcache"] = dcache, tcache
        return state

    # -------------------------------------------------------- generate
    def generate(self, prompt: List[int], max_new_tokens: int,
                 eos_id: Optional[int] = None, *, frame_embeds=None,
                 patch_embeds=None) -> GenResult:
        t0 = time.perf_counter()
        state = self.start_stream(prompt, frame_embeds=frame_embeds,
                                  patch_embeds=patch_embeds)
        res = state["res"]
        while not state["done"] and res.new_tokens < max_new_tokens:
            state = self.session_step(state, eos_id)
        res.wall_time_s = time.perf_counter() - t0
        return res


def autoregressive_baseline_cost(n_tokens: int, target: ModelBundle) -> float:
    """Modeled cost of plain target-only decoding."""
    return n_tokens * target.cost_per_token


# ===================================================================== tree

@functools.partial(jax.jit, static_argnames=("cfg", "spec"))
def _tree_forward(params, cfg, spec, cache, tokens, depths, mask, nodes):
    return T.tree_step(params, cfg, tokens, cache, spec, depths, mask, nodes)


@functools.partial(jax.jit, static_argnames=("cfg", "spec"))
def _tree_commit(cfg, spec, cache, nodes, path, n_commit):
    return T.commit_tree_path(cfg, cache, spec, nodes, path, n_commit)


class TreeSpecEngine(_StepMixin, _ShardingMixin):
    """Host-driven engine whose speculation step can be a TREE.

    The controller (``TapOutTreeSequence``) picks a speculation SHAPE per
    session: a chain + stop rule (the existing jitted chain primitives run
    unchanged) or a static ``TreeSpec`` topology.  A tree session:

      1. refeeds the sequence suffix through the draft model (the chain
         path's cache invariant), then expands the tree LEVEL BY LEVEL —
         each level is one jitted ``tree_step`` whose nodes attend the
         cache plus their carried ancestors under the ancestor mask; child
         tokens come from the parent's predictive distribution (top-k in
         greedy mode, i.i.d. samples in stochastic mode);
      2. verifies the whole tree in ONE target forward: the verify feed is
         ``[last committed token] + nodes`` (so the root distribution rides
         along exactly like the chain verifier's last-token feed);
      3. walks the LONGEST ACCEPTED PATH (``tree.verify_walk``) — greedy
         argmax matching, or SpecInfer-style recursive rejection with
         residual-distribution sampling at the divergence node;
      4. commits ONLY the accepted path: ``commit_tree_path`` scatters the
         path's K/V rows into the (dense or paged) cache and the usual
         O(1) pointer / length-truncation rollback does the rest.  Neither
         drafting nor verification ever writes an uncommitted row.

    Works on dense caches and (``paged=True``) on B=1 paged caches whose
    single stream owns the whole pool.  Requires attention/MLA-only stacks
    (recurrent state cannot fork per branch) with non-ring buffers.
    """

    backend_name = "tree"

    def __init__(self, draft: ModelBundle, target: ModelBundle,
                 controller: TapOutTreeSequence, *, max_len: int = 2048,
                 temperature: float = 0.0, greedy: bool = True,
                 cache_dtype=jnp.float32, kv_dtype: Optional[str] = None,
                 quant_draft: bool = False, seed: int = 0,
                 paged: bool = False, block_size: int = 64, mesh=None):
        if quant_draft:
            draft = quantized_bundle(draft)
        self.draft, self.target = draft, target
        self.mesh = mesh
        self._place_bundles()
        # precision arms (ShapeArm.precision == "int8") draft with a
        # quantized copy of the SAME draft weights — quantize once here,
        # the shape bandit then picks precision per session like any arm
        self._draft_variants: Dict[str, ModelBundle] = {}
        if (not quant_draft
                and any(s.precision == "int8" for s in controller.shapes)):
            self._draft_variants["int8"] = self._place_variant(
                quantized_bundle(self.draft))
        # per-engine jits when a mesh is bound (see fresh_session_jits)
        if mesh is None:
            self._tree_fwd, self._tree_cmt = _tree_forward, _tree_commit
            self._draft_chain, self._verify_chain = (
                (draft_session_paged, verify_session_paged) if paged
                else (draft_session, verify_session))
        else:
            self._tree_fwd = jax.jit(_tree_forward.__wrapped__,
                                     static_argnames=("cfg", "spec"))
            self._tree_cmt = jax.jit(_tree_commit.__wrapped__,
                                     static_argnames=("cfg", "spec"))
            self._draft_chain, self._verify_chain = fresh_session_jits(
                paged=paged)
        self.controller = controller
        self.gamma_max = controller.gamma_max
        self.max_len = max_len
        self.temperature = temperature
        self.greedy = greedy
        self.cache_dtype = cache_dtype
        self.kv_dtype = kv_dtype
        self.paged = paged
        self.block_size = block_size
        self.rng = jax.random.PRNGKey(seed)
        self._host_rng = np.random.default_rng(seed)
        self.collect_traces = False
        self._step_cache: Dict[tuple, callable] = {}
        if paged:
            _, self.dspec = T.init_paged_cache(
                draft.cfg, 1, max_len, block_size=block_size,
                pool_tokens=max_len, dtype=cache_dtype, kv_dtype=kv_dtype)
            _, self.tspec = T.init_paged_cache(
                target.cfg, 1, max_len, block_size=block_size,
                pool_tokens=max_len, dtype=cache_dtype, kv_dtype=kv_dtype)
        else:
            _, self.dspec = T.init_cache(draft.cfg, 1, max_len, cache_dtype,
                                         kv_dtype=kv_dtype)
            _, self.tspec = T.init_cache(target.cfg, 1, max_len, cache_dtype,
                                         kv_dtype=kv_dtype)
        for spec, cfg in ((self.dspec, draft.cfg), (self.tspec, target.cfg)):
            assert spec.cheap_rollback, \
                "tree speculation requires attn/mla-only stacks"
            assert all(not l.ring for l in spec.layers), \
                "tree speculation requires non-ring caches (max_len within " \
                "the full-cache budget)"
        self._max_overshoot = max(
            self.gamma_max,
            max((s.tree.max_depth + 1 for s in controller.shapes
                 if s.kind == "tree"), default=0))

    # -------------------------------------------------------- plumbing
    def _next_rng(self):
        self.rng, k = jax.random.split(self.rng)
        return k

    def _draft_bundle(self, shape) -> ModelBundle:
        """The draft weights a shape arm runs with (its precision axis)."""
        return self._draft_variants.get(shape.precision, self.draft)

    def _fresh_cache(self, which: str):
        bundle = self.draft if which == "draft" else self.target
        if self.paged:
            cache, spec = T.init_paged_cache(
                bundle.cfg, 1, self.max_len, block_size=self.block_size,
                pool_tokens=self.max_len, dtype=self.cache_dtype,
                kv_dtype=self.kv_dtype)
            # single stream owns the whole pool: identity block table
            tbl = np.arange(1, spec.max_blocks + 1, dtype=np.int32)[None]
            return self._place_cache({**cache, "tables": jnp.asarray(tbl)},
                                     paged=True)
        cache, _ = T.init_cache(bundle.cfg, 1, self.max_len, self.cache_dtype,
                                kv_dtype=self.kv_dtype)
        return self._place_cache(cache)

    def _rollback(self, cache, n: int):
        return paged_rollback(cache, [n]) if self.paged else rollback(cache, n)

    def _feed(self, which: str, cache, tokens: List[int],
              bundle: Optional[ModelBundle] = None):
        """Advance by ``tokens``, returning (last-token logits, cache).
        ``bundle`` overrides the weights (precision arms feed through their
        own draft copy); the jitted wrapper is shared — params are traced
        arguments, so a different pytree structure just retraces."""
        key = (which, "feed", len(tokens), self.paged)
        if key not in self._step_cache:
            cfg = (self.draft if which == "draft" else self.target).cfg
            spec = self.dspec if which == "draft" else self.tspec
            step = T.paged_step if self.paged else T.step

            @jax.jit
            def fn(params, toks, cache):
                return step(params, cfg, toks, cache, spec)
            self._step_cache[key] = fn
        if bundle is None:
            bundle = self.draft if which == "draft" else self.target
        return self._step_cache[key](bundle.params,
                                     jnp.asarray([tokens], jnp.int32), cache)

    def _prefill(self, which: str, cache, tokens: List[int],
                 chunk: int = 16):
        toks = list(tokens)
        n_chunks = len(toks) // chunk
        for i in range(n_chunks):
            _, cache = self._feed(which, cache, toks[i * chunk:(i + 1) * chunk])
        for j in range(n_chunks * chunk, len(toks)):
            _, cache = self._feed(which, cache, toks[j:j + 1])
        return cache

    # -------------------------------------------------------- streams
    @_on_mesh
    def start_stream(self, prompt: List[int]) -> dict:
        assert len(prompt) >= 2, "need >= 2 prompt tokens"
        assert len(prompt) + self._max_overshoot + 2 <= self.max_len
        seq = list(prompt)
        res = GenResult(tokens=seq, prompt_len=len(prompt))
        dcache = self._prefill("draft", self._fresh_cache("draft"), seq[:-1])
        tcache = self._prefill("target", self._fresh_cache("target"), seq[:-1])
        return {"seq": seq, "res": res, "dcache": dcache, "tcache": tcache,
                "done": False}

    # -------------------------------------------------------- sessions
    def _chain_session(self, state: dict, stop_idx: int,
                       draft: ModelBundle):
        """One chain draft/verify session (the existing jitted primitives,
        dense or paged-B=1, with the shape's stop rule broadcast; ``draft``
        carries the shape arm's precision — bf16 or int8 weights)."""
        seq = state["seq"]
        L = len(seq)
        g = self.gamma_max
        arm_per_pos = np.full((g,), stop_idx, np.int32)
        lam = jnp.float32(self.controller.lam)
        if self.paged:
            dcache_in = self._rollback(state["dcache"], L - 2)
            active = jnp.asarray([True])
            dres = self._draft_chain(
                draft.params, draft.cfg, self.dspec, dcache_in,
                jnp.asarray([seq[-2:]], jnp.int32), jnp.asarray(arm_per_pos[None]),
                lam, self._next_rng()[None], active,
                arms=self.controller.arms, gamma_max=g,
                temperature=self.temperature)
            vres = self._verify_chain(
                self.target.params, self.target.cfg, self.tspec,
                state["tcache"], jnp.asarray([seq[-1:]], jnp.int32),
                dres.tokens, dres.n_drafted, dres.qprobs,
                self._next_rng()[None], active, gamma_max=g,
                temperature=self.temperature, greedy=self.greedy)
        else:
            dcache_in = self._rollback(state["dcache"], L - 2)
            dres = self._draft_chain(
                draft.params, draft.cfg, self.dspec, dcache_in,
                jnp.asarray([seq[-2:]], jnp.int32), jnp.asarray(arm_per_pos),
                lam, self._next_rng(), arms=self.controller.arms, gamma_max=g,
                temperature=self.temperature)
            vres = self._verify_chain(
                self.target.params, self.target.cfg, self.tspec,
                state["tcache"], jnp.asarray([seq[-1:]], jnp.int32),
                dres.tokens, dres.n_drafted, dres.qprobs, self._next_rng(),
                gamma_max=g, temperature=self.temperature, greedy=self.greedy)
        n_drafted = int(dres.n_drafted[0])
        m = int(vres.n_accepted[0])
        out = np.asarray(vres.out_tokens[0, :m + 1]).tolist()
        state["dcache"] = self._rollback(dres.cache, L + m - 1)
        state["tcache"] = self._rollback(vres.cache, L + m)
        cost = modeled_session_cost(n_drafted + 1, draft.cost_per_token,
                                    self.target.cost_per_token)
        return n_drafted, m, out, cost

    def _tree_session(self, state: dict, tree: TreeSpec,
                      draft: ModelBundle):
        """One tree draft/verify session (see class docstring)."""
        seq = state["seq"]
        L = len(seq)
        cfg_d, cfg_t = draft.cfg, self.target.cfg
        Tn = tree.n_nodes
        temp = self.temperature
        greedy_draft = self.greedy or temp == 0.0

        # ---- draft: refeed suffix, then expand level by level
        dcache = self._rollback(state["dcache"], L - 2)
        lg, dcache = self._feed("draft", dcache, seq[-2:], bundle=draft)
        parent_dist = {-1: np.asarray(_probs(lg[0, -1], temp))}
        # greedy sibling RANKING uses raw logits: at temperature 0 the
        # sampling distribution's non-top-1 entries underflow to exactly
        # 0.0 and argsort would tie-break the tail arbitrarily, collapsing
        # every multi-branch tree to its top-1 path
        parent_rank = {-1: np.asarray(lg[0, -1], np.float32)}
        tokens = np.zeros(Tn, np.int64)
        qdist = np.zeros((Tn, cfg_d.vocab_size), np.float32)
        anc = tree.ancestor_mask
        nodes = T.init_tree_nodes(cfg_d, 1)
        fed = 0
        for level in tree.levels:
            for p in ({-1} if fed == 0 else
                      dict.fromkeys(tree.parents[i] for i in level)):
                dist = parent_dist[p]
                cands = tree.roots if p == -1 else tree.children[p]
                if greedy_draft:
                    picks = np.argsort(parent_rank[p])[::-1][:len(cands)]
                else:
                    picks = self._host_rng.choice(
                        dist.size, size=len(cands), p=dist / dist.sum())
                for node, tok in zip(cands, picks):
                    tokens[node] = int(tok)
                    qdist[node] = dist
            lvl = list(level)
            # draft pointer sits at L after the refeed, so a node's
            # position is pointer + its depth (roots at L, etc.)
            lg_lvl, nodes = self._tree_fwd(
                draft.params, cfg_d, self.dspec, dcache,
                jnp.asarray([tokens[lvl]], jnp.int32),
                jnp.asarray(tree.depths[lvl], jnp.int32),
                jnp.asarray(anc[np.ix_(lvl, range(fed + len(lvl)))]),
                nodes)
            fed += len(lvl)
            if fed < Tn:                 # leaves' dists are never expanded
                probs_lvl = np.asarray(_probs(lg_lvl[0], temp))
                lg_np = np.asarray(lg_lvl[0], np.float32)
                for j, node in enumerate(lvl):
                    parent_dist[node] = probs_lvl[j]
                    parent_rank[node] = lg_np[j]

        # ---- verify: [last token] + tree in ONE target pass
        vtokens = np.concatenate([[seq[-1]], tokens])
        lg_v, tnodes = self._tree_fwd(
            self.target.params, cfg_t, self.tspec, state["tcache"],
            jnp.asarray([vtokens], jnp.int32),
            jnp.asarray(tree.verify_depths, jnp.int32),
            jnp.asarray(tree.verify_mask), T.init_tree_nodes(cfg_t, 1))
        p_node = np.asarray(_probs(lg_v[0], temp))

        # ---- longest accepted path + residual sampling at divergence
        path, repl = verify_walk(tree, tokens, qdist, p_node,
                                 greedy=self.greedy, rng=self._host_rng)
        m = len(path)
        out = [int(tokens[i]) for i in path] + [int(repl)]

        # ---- commit ONLY the accepted path, O(1) rollback
        P_t = 1 + tree.max_depth
        vpath = np.zeros(P_t, np.int32)
        vpath[:m + 1] = [0] + [1 + i for i in path]
        tcache = self._tree_cmt(cfg_t, self.tspec, state["tcache"], tnodes,
                              jnp.asarray(vpath), m + 1)
        state["tcache"] = self._rollback(tcache, L + m)
        P_d = tree.max_depth
        dpath = np.zeros(P_d, np.int32)
        dpath[:m] = path
        dcache = self._tree_cmt(cfg_d, self.dspec, dcache, nodes,
                              jnp.asarray(dpath), m)
        state["dcache"] = self._rollback(dcache, L + m - 1)
        cost = modeled_session_cost(Tn + 1, draft.cost_per_token,
                                    self.target.cost_per_token)
        return Tn, m, out, cost

    @_on_mesh
    def session_step(self, state: dict, eos_id: Optional[int] = None) -> dict:
        """Run ONE shape-bandit session on a stream."""
        seq, res = state["seq"], state["res"]
        shape_idx = self.controller.begin_shape()
        shape = self.controller.shapes[shape_idx]
        dbundle = self._draft_bundle(shape)
        if shape.kind == "tree":
            n_drafted, m, out, cost = self._tree_session(state, shape.tree,
                                                         dbundle)
        else:
            n_drafted, m, out, cost = self._chain_session(
                state, self.controller.stop_arm_index(shape_idx), dbundle)
        seq.extend(out)
        self.controller.update_shape(shape_idx, n_drafted, m)
        res.sessions.append(SessionStats(n_drafted, m, shape_idx))
        res.modeled_cost += cost
        if eos_id is not None and eos_id in out:
            seq[:] = seq[:len(seq) - len(out) + out.index(eos_id) + 1]
            state["done"] = True
        if len(seq) + self._max_overshoot + 2 >= self.max_len:
            state["done"] = True
        return state

    # -------------------------------------------------------- generate
    def generate(self, prompt: List[int], max_new_tokens: int,
                 eos_id: Optional[int] = None) -> GenResult:
        t0 = time.perf_counter()
        state = self.start_stream(prompt)
        res = state["res"]
        while not state["done"] and res.new_tokens < max_new_tokens:
            state = self.session_step(state, eos_id)
        res.wall_time_s = time.perf_counter() - t0
        return res


class TreeSlotEngine(TreeSpecEngine):
    """Slot facade over the tree engine (``EngineSpec(backend="tree_slot")``).

    B per-slot stream states (each with its own single-stream cache pair)
    share ONE shape bandit, online across requests — the TapOut deployment
    setting with tree shapes in the arm pool.  A tick runs one session per
    active slot (a host loop over the jitted per-shape programs; a fused
    batched tree session is future work — topologies differ per slot, so
    it needs per-shape program pools like the chain engines').
    """

    backend_name = "tree_slot"

    def __init__(self, draft: ModelBundle, target: ModelBundle,
                 controller: TapOutTreeSequence, *, batch_size: int = 4,
                 **kw):
        super().__init__(draft, target, controller, **kw)
        self.batch_size = batch_size
        self.slots: List[Optional[dict]] = [None] * batch_size
        self._pending: Optional[dict] = None

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def open_stream(self, slot: int, prompt: List[int],
                    eos_id: Optional[int] = None) -> dict:
        assert self.slots[slot] is None, f"slot {slot} busy"
        st = self.start_stream(prompt)
        st["eos_id"] = eos_id
        self.slots[slot] = st
        return st

    def close_stream(self, slot: int) -> dict:
        st = self.slots[slot]
        assert st is not None
        self.slots[slot] = None
        return st

    def session_step_batch(self) -> List[int]:
        self.session_step_launch()
        return self.session_step_flush()

    # the tree tick is host-driven (per-shape jitted programs per slot), so
    # launch/flush degenerate to run-then-report — but exposing the same
    # two-phase protocol lets the server drive every backend identically
    def session_step_launch(self) -> bool:
        assert self._pending is None, "previous tick not flushed"
        acted: List[int] = []
        for s, st in enumerate(self.slots):
            if st is not None and not st["done"]:
                self.session_step(st, st.get("eos_id"))
                acted.append(s)
        if not acted:
            return False
        self._pending = {"acted": acted}
        return True

    def session_step_flush(self) -> List[int]:
        pending, self._pending = self._pending, None
        return pending["acted"] if pending else []


# ===================================================================== batched

def _tree_get_slot(tree, s: int):
    """Extract lane ``s`` from a slot-stacked cache pytree."""
    return jax.tree.map(lambda a: a[s], tree)


def _tree_set_slot(tree, s: int, lane):
    """Write lane ``s`` of a slot-stacked cache pytree (functional)."""
    return jax.tree.map(lambda big, one: big.at[s].set(one), tree, lane)


class BatchedSpecEngine(_StepMixin, _ShardingMixin):
    """Fixed-B slot engine: ONE jitted draft/verify program serves B streams.

    Per-slot B=1 caches are stacked on a leading slot axis, so every lane
    carries its own ``pos`` scalar and per-layer position arrays — streams
    at different sequence positions coexist in one program.  A tick runs one
    draft+verify session for every active slot at once; finished/empty
    slots ride along masked (outputs zeroed on device, cache lanes
    reconciled by the batched rollback below).

    Rollback after a tick:
      * pointer caches (attention/MLA): one vectorized write of the (B,)
        ``pos`` vector — stale K/V rows carry future positions and are
        masked by attention's ``kpos <= qpos`` rule (same invariant as the
        single-stream engine, now per lane);
      * recurrent caches (mamba2/rglru): restore the whole pre-tick
        snapshot (free in functional JAX), then re-advance each active lane
        by its accepted tokens (per-lane recompute — sequential state has
        no pointer to rewind).

    The batched session program compiles ONCE per (B, gamma_max); admission
    into a free slot never recompiles it (prefill uses chunked feeds of at
    most two shapes, see ``_prefill``).

    ``fused=True`` (the default, requires cheap-rollback caches on both
    models) additionally collapses the whole tick — input-side rollback,
    draft while-loop, verify, accept, output-side rollback — into ONE
    device program (``spec_decode.fused_session_tick``) and splits the tick
    into ``session_step_launch`` / ``session_step_flush`` so the serving
    loop can overlap tick t's device work with tick t-1's host accounting.
    The fused program runs the exact traced bodies of the synchronous
    primitives, so outcomes — and the bandit state they produce — are
    bit-identical to ``fused=False``.
    """

    backend_name = "batched"

    def __init__(self, draft: ModelBundle, target: ModelBundle,
                 controller: Controller, *, batch_size: int = 4,
                 max_len: int = 2048, temperature: float = 0.0,
                 greedy: bool = True, cache_dtype=jnp.float32,
                 kv_dtype: Optional[str] = None, quant_draft: bool = False,
                 seed: int = 0, prefill_chunk: int = 16, fused: bool = True,
                 mesh=None, drafters=None):
        assert batch_size >= 1
        if drafters is not None:
            # heterogeneous pool: the pool's DEFAULT drafter becomes the
            # engine's draft bundle; the rest get per-drafter lanes below
            draft = drafters.bundle(drafters.default)
        if quant_draft:
            draft = quantized_bundle(draft)
        self.draft, self.target = draft, target
        self.mesh = mesh
        self._place_bundles()
        self.controller = controller
        self.gamma_max = controller.gamma_max
        self.batch_size = batch_size
        self.max_len = max_len
        self.temperature = temperature
        self.greedy = greedy
        self.cache_dtype = cache_dtype
        self.kv_dtype = kv_dtype
        self.prefill_chunk = prefill_chunk
        self.rng = jax.random.PRNGKey(seed)
        self.collect_traces = False
        self._step_cache: Dict[tuple, callable] = {}

        dc1, self.dspec = T.init_cache(draft.cfg, 1, max_len, cache_dtype,
                                       kv_dtype=kv_dtype)
        tc1, self.tspec = T.init_cache(target.cfg, 1, max_len, cache_dtype,
                                       kv_dtype=kv_dtype)
        self.draft_cheap = self.dspec.cheap_rollback
        self.target_cheap = self.tspec.cheap_rollback
        # fresh per-admission lanes live on the mesh device set too, so the
        # prefilled lane and the stacked caches it is written into agree
        self._fresh_dcache = self._place_cache(dc1)
        self._fresh_tcache = self._place_cache(tc1)
        stack = lambda c: jax.tree.map(
            lambda a: jnp.stack([a] * batch_size), c)
        # slot lanes shard over the ("pod","data") batch axes
        self.dcaches = self._place_cache(stack(dc1), slots=True)
        self.tcaches = self._place_cache(stack(tc1), slots=True)
        self._sharded_sessions = None
        if mesh is not None:
            from repro.launch.shardings import slot_cache_shardings
            self._sharded_sessions = make_sharded_sessions(
                mesh, cfg_d=self.draft.cfg, cfg_t=self.target.cfg,
                dspec=self.dspec, tspec=self.tspec,
                dparams_sh=self._dparams_sh, tparams_sh=self._tparams_sh,
                dcache_sh=slot_cache_shardings(mesh, self.dcaches),
                tcache_sh=slot_cache_shardings(mesh, self.tcaches),
                batch_size=batch_size, gamma_max=self.gamma_max,
                arms=controller.arms, temperature=temperature, greedy=greedy,
                n_prompt_tokens=2 if self.draft_cheap else 1, paged=False)

        # fused single-dispatch tick: needs O(1) pointer rollback on BOTH
        # models (recurrent state falls back to the two-dispatch tick with
        # host-side snapshot-recompute)
        self.fused = bool(fused and self.draft_cheap and self.target_cheap)
        self._fused_tick = None
        if self.fused:
            if mesh is None:
                self._fused_tick = self._meshless_fused(paged=False)
            else:
                from repro.launch.shardings import slot_cache_shardings
                self._fused_tick = make_sharded_fused(
                    mesh, cfg_d=self.draft.cfg, cfg_t=self.target.cfg,
                    dspec=self.dspec, tspec=self.tspec,
                    dparams_sh=self._dparams_sh, tparams_sh=self._tparams_sh,
                    dcache_sh=slot_cache_shardings(mesh, self.dcaches),
                    tcache_sh=slot_cache_shardings(mesh, self.tcaches),
                    batch_size=batch_size, gamma_max=self.gamma_max,
                    arms=controller.arms, temperature=temperature,
                    greedy=greedy, n_prompt_tokens=2, paged=False)

        B = batch_size
        self.slots: List[Optional[dict]] = [None] * B
        self._pending: Optional[dict] = None
        # host mirrors of each lane's cache "pos" (invariant: len(seq)-1
        # for target, len(seq)-2 for pointer-rollback draft caches; updated
        # IN PLACE so drafter-pool runtimes can alias them)
        self._dpos = np.zeros(B, np.int64)
        self._tpos = np.zeros(B, np.int64)

        # ---- heterogeneous drafter pool (drafter identity as an arm axis)
        self.drafters = drafters
        self._dr: Optional[Dict[str, dict]] = None
        if drafters is not None:
            self._init_drafter_pool(fused)

    # ---------------------------------------------------- drafter pool
    def _init_drafter_pool(self, fused_flag: bool) -> None:
        """One runtime per candidate drafter: placed weights, a fresh B=1
        lane, slot-stacked caches, a host pos mirror, and EITHER a fused
        tick (cheap-rollback drafters) or the per-drafter statics for the
        synchronous two-dispatch tick (recurrent SSD state).  All jitted
        programs are per-drafter entries in the SAME module-level trace
        caches, so the host bandit can switch drafters between ticks with
        zero re-traces after warmup."""
        pool, ctrl, B = self.drafters, self.controller, self.batch_size
        assert hasattr(ctrl, "begin_shape") and hasattr(ctrl, "shapes"), \
            "drafter-pool serving needs a shape controller (TapOutTreeSequence)"
        names = set(pool.names)
        for sh in ctrl.shapes:
            assert sh.kind == "chain", \
                f"drafter-pool serving drafts chains, got {sh.name}"
            assert (sh.drafter or pool.default) in names, sh.drafter
        self._dr = {}
        for d in pool:
            if d.name == pool.default:
                rt = {"name": d.name, "bundle": self.draft,
                      "spec": self.dspec, "cheap": self.draft_cheap,
                      "fresh": self._fresh_dcache, "caches": self.dcaches,
                      "pos": self._dpos, "sh": self._dparams_sh}
            else:
                bundle, sh = d.bundle, None
                if self.mesh is not None:
                    from repro.launch.shardings import params_shardings
                    sh = params_shardings(self.mesh, bundle.params,
                                          mode="serve")
                    bundle = ModelBundle(jax.device_put(bundle.params, sh),
                                         bundle.cfg,
                                         cost_per_token=bundle.cost_per_token)
                dc1, spec = T.init_cache(bundle.cfg, 1, self.max_len,
                                         self.cache_dtype,
                                         kv_dtype=self.kv_dtype)
                stack = lambda c: jax.tree.map(
                    lambda a: jnp.stack([a] * B), c)
                rt = {"name": d.name, "bundle": bundle, "spec": spec,
                      "cheap": spec.cheap_rollback,
                      "fresh": self._place_cache(dc1),
                      "caches": self._place_cache(stack(dc1), slots=True),
                      "pos": np.zeros(B, np.int64), "sh": sh}
            rt["fused"] = bool(fused_flag and rt["cheap"] and
                               self.target_cheap)
            rt["tick"] = rt["sessions"] = None
            if rt["fused"]:
                if self.mesh is None:
                    rt["tick"] = (self._fused_tick
                                  if rt["name"] == pool.default and self.fused
                                  else self._meshless_fused(
                                      paged=False, draft=rt["bundle"],
                                      dspec=rt["spec"]))
                else:
                    from repro.launch.shardings import slot_cache_shardings
                    rt["tick"] = make_sharded_fused(
                        self.mesh, cfg_d=rt["bundle"].cfg,
                        cfg_t=self.target.cfg, dspec=rt["spec"],
                        tspec=self.tspec, dparams_sh=rt["sh"],
                        tparams_sh=self._tparams_sh,
                        dcache_sh=slot_cache_shardings(self.mesh,
                                                       rt["caches"]),
                        tcache_sh=slot_cache_shardings(self.mesh,
                                                       self.tcaches),
                        batch_size=B, gamma_max=self.gamma_max,
                        arms=ctrl.arms, temperature=self.temperature,
                        greedy=self.greedy, n_prompt_tokens=2, paged=False)
            elif self.mesh is not None:
                from repro.launch.shardings import slot_cache_shardings
                rt["sessions"] = make_sharded_sessions(
                    self.mesh, cfg_d=rt["bundle"].cfg, cfg_t=self.target.cfg,
                    dspec=rt["spec"], tspec=self.tspec, dparams_sh=rt["sh"],
                    tparams_sh=self._tparams_sh,
                    dcache_sh=slot_cache_shardings(self.mesh, rt["caches"]),
                    tcache_sh=slot_cache_shardings(self.mesh, self.tcaches),
                    batch_size=B, gamma_max=self.gamma_max, arms=ctrl.arms,
                    temperature=self.temperature, greedy=self.greedy,
                    n_prompt_tokens=2 if rt["cheap"] else 1, paged=False)
            self._dr[d.name] = rt

    def _set_dr_caches(self, name: str, caches) -> None:
        """Adopt a drafter's post-tick/post-catch-up stacked caches; the
        default drafter's runtime and ``self.dcaches`` stay one object."""
        self._dr[name]["caches"] = caches
        if name == self.drafters.default:
            self.dcaches = caches

    def _sync_drafter_lanes(self, rt: dict, act_idx) -> None:
        """Lazy catch-up: before a drafter ticks, feed each active lane the
        tokens it missed while OTHER drafters were drafting (its cache
        consumed ``pos`` tokens; a cheap-rollback drafter needs len(seq)-2,
        a recurrent one len(seq)-1).  Feeds go through the canonical
        ``_chunk_schedule`` windows — {prefill_chunk, 1} shapes only — so
        catch-up compiles nothing new after warmup."""
        need = {}
        for s in act_idx:
            n = len(self.slots[s]["seq"]) - (2 if rt["cheap"] else 1)
            if int(rt["pos"][s]) < n:
                need[s] = n
        if not need:
            return
        tag = f"draft:{rt['name']}"
        lanes = []
        for s in range(self.batch_size):
            lane = _tree_get_slot(rt["caches"], s)
            if s in need:
                q = int(rt["pos"][s])
                toks = np.asarray(self.slots[s]["seq"][q:need[s]],
                                  np.int32)[None]
                for lo, hi in _chunk_schedule(toks.shape[1],
                                              self.prefill_chunk):
                    lane = self._advance_with(tag, rt["bundle"], rt["spec"],
                                              lane, toks[:, lo:hi])
                rt["pos"][s] = need[s]
            lanes.append(lane)
        self._set_dr_caches(rt["name"], self._place_cache(
            jax.tree.map(lambda *xs: jnp.stack(xs), *lanes), slots=True))

    # -------------------------------------------------------- helpers
    def _prefill(self, which: str, params, cache, tokens: List[int]):
        """Advance a fresh B=1 cache by ``tokens`` using chunked feeds, so
        prefill compiles at most two shapes (chunk + single) instead of one
        program per prompt length."""
        toks = np.asarray(tokens, np.int32)[None]
        C = self.prefill_chunk
        n_chunks = toks.shape[1] // C
        for i in range(n_chunks):
            cache = self._advance(which, params, cache, toks[:, i * C:(i + 1) * C])
        for j in range(n_chunks * C, toks.shape[1]):
            cache = self._advance(which, params, cache, toks[:, j:j + 1])
        return cache

    def _next_rng(self, n: int = 1):
        keys = jax.random.split(self.rng, n + 1)
        self.rng = keys[0]
        return keys[1:]

    # -------------------------------------------------------- slots
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def active_mask(self) -> np.ndarray:
        return np.array([s is not None and not s["done"] for s in self.slots])

    @_on_mesh
    def open_stream(self, slot: int, prompt: List[int],
                    eos_id: Optional[int] = None) -> dict:
        """Prefill ``prompt`` into a free slot; the stream participates in
        every subsequent ``session_step_batch`` until closed."""
        assert self.slots[slot] is None, f"slot {slot} busy"
        assert len(prompt) >= 2, "need >= 2 prompt tokens"
        seq = list(prompt)
        pre = seq[:-1]                       # invariant: pos = len(seq) - 1
        dcache = self._prefill("draft", self.draft.params,
                               self._fresh_dcache, pre)
        tcache = self._prefill("target", self.target.params,
                               self._fresh_tcache, pre)
        # re-pin the canonical slot shardings: the eager lane write lets
        # GSPMD propagate whatever layout it likes, and the sharded session
        # program's in_shardings require the canonical one
        self.dcaches = self._place_cache(
            _tree_set_slot(self.dcaches, slot, dcache), slots=True)
        self.tcaches = self._place_cache(
            _tree_set_slot(self.tcaches, slot, tcache), slots=True)
        self._dpos[slot] = len(pre)
        self._tpos[slot] = len(pre)
        if self._dr is not None:
            # the default drafter's runtime adopts the prefilled lane; every
            # OTHER drafter's lane resets to a fresh cache (recurrent SSD
            # state MUST restart from zero) and catches up lazily before
            # its first tick on this stream
            self._dr[self.drafters.default]["caches"] = self.dcaches
            for name, rt in self._dr.items():
                if name == self.drafters.default:
                    continue
                rt["caches"] = self._place_cache(
                    _tree_set_slot(rt["caches"], slot, rt["fresh"]),
                    slots=True)
                rt["pos"][slot] = 0
        st = {"seq": seq, "res": GenResult(tokens=seq, prompt_len=len(prompt)),
              "done": False, "eos_id": eos_id}
        self.slots[slot] = st
        return st

    def close_stream(self, slot: int) -> dict:
        """Release a slot (its cache lane is dead until the next admission)."""
        st = self.slots[slot]
        assert st is not None
        self.slots[slot] = None
        self._dpos[slot] = 0
        self._tpos[slot] = 0
        if self._dr is not None:
            for rt in self._dr.values():
                rt["pos"][slot] = 0
        return st

    # -------------------------------------------------------- tick
    def session_step_batch(self) -> List[int]:
        """Run one draft/verify session for every active slot in one
        batched program (one synchronous tick: launch + flush back to
        back).  Returns the slots that were active this tick."""
        self.session_step_launch()
        return self.session_step_flush()

    @_on_mesh
    def session_step_launch(self) -> bool:
        """Dispatch one tick WITHOUT reading its outcomes back.

        Fused path: the only host work is input assembly and the bandit's
        arm draw (``begin_batch``); the single device program is launched
        asynchronously and its ``FusedTick`` outcome buffer stays
        device-resident until ``session_step_flush``.  The serving loop
        flushes tick t-1 only after admitting for tick t, so the bandit
        consumes outcomes one step behind — its begin/update call sequence
        is exactly the synchronous path's, keeping its state bit-identical.
        Non-fused engines run the classic two-dispatch tick here and merely
        stash the acted list for flush.  Returns True iff a tick ran."""
        assert self._pending is None, "previous tick not flushed"
        B, g = self.batch_size, self.gamma_max
        active = self.active_mask()
        act_idx = np.flatnonzero(active)
        if act_idx.size == 0:
            return False
        if self._dr is not None:
            return self._launch_drafter_tick(active, act_idx)
        if not self.fused:
            self._pending = {"acted": self._session_step_sync()}
            return True

        L = np.array([len(self.slots[s]["seq"]) if self.slots[s] else 0
                      for s in range(B)], np.int64)
        arm_mat = np.zeros((B, g), np.int32)
        arm_mat[act_idx] = self.controller.begin_batch(act_idx.size)
        in_toks = np.zeros((B, 2), np.int32)
        last_toks = np.zeros((B, 1), np.int32)
        for s in act_idx:
            seq = self.slots[s]["seq"]
            in_toks[s] = seq[-2:]
            last_toks[s, 0] = seq[-1]
        keys = self._next_rng(2 * B)
        ft = self._fused_tick(
            self.draft.params, self.target.params, self.dcaches,
            self.tcaches, jnp.asarray(in_toks), jnp.asarray(last_toks),
            jnp.asarray(arm_mat), jnp.float32(self.controller.lam),
            keys[:B], keys[B:], jnp.asarray(active),
            jnp.asarray(L, jnp.int32), jnp.asarray(self._dpos, jnp.int32),
            jnp.asarray(self._tpos, jnp.int32))
        # caches come back already rolled back — adopt them immediately so
        # admissions between ticks write into post-tick lanes
        self.dcaches, self.tcaches = ft.dcache, ft.tcache
        self._pending = {"act_idx": act_idx, "active": active,
                         "arm_mat": arm_mat, "L": L, "ft": ft}
        return True

    def _launch_drafter_tick(self, active, act_idx) -> bool:
        """One tick of the heterogeneous-drafter engine: the host
        meta-bandit picks ONE (drafter, stop-rule) arm for the whole batch
        (``begin_shape``), the chosen drafter's lanes catch up on tokens
        accepted while other drafters ran, then its pre-built fused tick
        (cheap-rollback drafters) or synchronous two-dispatch tick
        (recurrent SSD) launches — no re-trace, just a different cached
        program."""
        B, g = self.batch_size, self.gamma_max
        ctrl = self.controller
        shape_idx = int(ctrl.begin_shape())
        rt = self._dr[ctrl.drafter_for(shape_idx) or self.drafters.default]
        self._sync_drafter_lanes(rt, act_idx)
        arm_mat = np.zeros((B, g), np.int32)
        arm_mat[act_idx] = ctrl.stop_arm_index(shape_idx)
        if not rt["fused"]:
            acted = self._session_step_sync(rt=rt, shape_idx=shape_idx,
                                            arm_mat=arm_mat)
            self._pending = {"acted": acted}
            return True
        L = np.array([len(self.slots[s]["seq"]) if self.slots[s] else 0
                      for s in range(B)], np.int64)
        in_toks = np.zeros((B, 2), np.int32)
        last_toks = np.zeros((B, 1), np.int32)
        for s in act_idx:
            seq = self.slots[s]["seq"]
            in_toks[s] = seq[-2:]
            last_toks[s, 0] = seq[-1]
        keys = self._next_rng(2 * B)
        ft = rt["tick"](
            rt["bundle"].params, self.target.params, rt["caches"],
            self.tcaches, jnp.asarray(in_toks), jnp.asarray(last_toks),
            jnp.asarray(arm_mat), jnp.float32(ctrl.lam),
            keys[:B], keys[B:], jnp.asarray(active),
            jnp.asarray(L, jnp.int32), jnp.asarray(rt["pos"], jnp.int32),
            jnp.asarray(self._tpos, jnp.int32))
        self._set_dr_caches(rt["name"], ft.dcache)
        self.tcaches = ft.tcache
        self._pending = {"act_idx": act_idx, "active": active,
                         "arm_mat": arm_mat, "L": L, "ft": ft,
                         "shape_idx": shape_idx, "drafter": rt["name"]}
        return True

    @_on_mesh
    def session_step_flush(self) -> List[int]:
        """Read the pending tick's device-resident outcomes, do per-stream
        accounting (sequence extension, stats, EOS/budget termination) and
        feed the bandit (``update_batch``).  Returns the acted slots; [] if
        no tick is pending."""
        pending, self._pending = self._pending, None
        if pending is None:
            return []
        if "acted" in pending:              # non-fused tick already complete
            return pending["acted"]
        active, act_idx = pending["active"], pending["act_idx"]
        arm_mat, L, ft = pending["arm_mat"], pending["L"], pending["ft"]
        drafter = pending.get("drafter")
        g = self.gamma_max
        c_d = (self._dr[drafter]["bundle"].cost_per_token if drafter
               else self.draft.cost_per_token)
        c_t = self.target.cost_per_token
        nd = np.asarray(ft.n_drafted)
        m = np.asarray(ft.n_accepted)
        out_all = np.asarray(ft.out_tokens)
        if self.collect_traces:
            sig_all = np.asarray(ft.signals)
            ent_all = np.asarray(ft.entropies)
        for s in act_idx:
            st = self.slots[s]
            seq, res = st["seq"], st["res"]
            out = out_all[s, :m[s] + 1].tolist()
            seq.extend(out)
            # drafter ticks record the META-arm (shape_idx); plain ticks
            # record the stop-rule arm as before
            arm = (int(pending["shape_idx"]) if drafter
                   else int(arm_mat[s, 0]))
            res.sessions.append(SessionStats(int(nd[s]), int(m[s]), arm))
            res.modeled_cost += modeled_session_cost(int(nd[s]) + 1, c_d, c_t)
            if self.collect_traces:
                res.traces.append({
                    "signals": sig_all[s], "entropies": ent_all[s],
                    "n_drafted": int(nd[s]), "n_accepted": int(m[s]),
                    "position_base": 0})
            eos = st["eos_id"]
            if eos is not None and eos in out:
                seq[:] = seq[:len(seq) - len(out) + out.index(eos) + 1]
                st["done"] = True
            if len(seq) + g + 2 >= self.max_len:
                st["done"] = True
        # host mirrors follow the on-device output-side rollback (in place:
        # drafter-pool runtimes alias these arrays)
        self._tpos[:] = np.where(active, L + m, self._tpos)
        if drafter:
            rt = self._dr[drafter]
            rt["pos"][:] = np.where(active, L + m - 1, rt["pos"])
            self.controller.update_shape_batch(pending["shape_idx"],
                                               nd[act_idx], m[act_idx])
        else:
            self._dpos[:] = np.where(active, L + m - 1, self._dpos)
            self.controller.update_batch(arm_mat[act_idx], nd[act_idx],
                                         m[act_idx])
        return act_idx.tolist()

    def _session_step_sync(self, rt: Optional[dict] = None,
                           shape_idx: Optional[int] = None,
                           arm_mat: Optional[np.ndarray] = None) -> List[int]:
        """The classic two-dispatch tick (snapshot-recompute rollback for
        recurrent stacks lives here — fusion requires cheap rollback).

        With ``rt`` (a drafter-pool runtime) the draft side runs that
        drafter's bundle/spec/caches instead of the engine defaults, the
        stop-rule row matrix is supplied by the caller (one meta-arm for the
        whole tick), and the bandit is fed through
        ``update_shape_batch(shape_idx, ...)`` — this is how the recurrent
        SSD drafter serves inside the drafter-pool engine."""
        B, g = self.batch_size, self.gamma_max
        active = self.active_mask()
        act_idx = np.flatnonzero(active)
        if act_idx.size == 0:
            return []
        dbundle = rt["bundle"] if rt else self.draft
        dspec = rt["spec"] if rt else self.dspec
        dcheap = rt["cheap"] if rt else self.draft_cheap
        dcaches_cur = rt["caches"] if rt else self.dcaches
        dpos_arr = rt["pos"] if rt else self._dpos
        sessions = rt["sessions"] if rt else self._sharded_sessions
        c_d = dbundle.cost_per_token
        c_t = self.target.cost_per_token
        L = np.array([len(self.slots[s]["seq"]) if self.slots[s] else 0
                      for s in range(B)], np.int64)

        # ---- controller: per-stream arm rows (inactive rows are arm 0)
        if arm_mat is None:
            arm_mat = np.zeros((B, g), np.int32)
            arm_mat[act_idx] = self.controller.begin_batch(act_idx.size)

        # ---- assemble per-stream inputs
        n_in = 2 if dcheap else 1
        in_toks = np.zeros((B, n_in), np.int32)
        last_toks = np.zeros((B, 1), np.int32)
        for s in act_idx:
            seq = self.slots[s]["seq"]
            in_toks[s] = seq[-n_in:]
            last_toks[s, 0] = seq[-1]

        if dcheap:
            dpos_in = np.where(active, L - 2, dpos_arr)
            dcaches_in = {**dcaches_cur,
                          "pos": jnp.asarray(dpos_in, jnp.int32)}
            dsnap = None
        else:
            dsnap = dcaches_cur
            dcaches_in = dcaches_cur
        tsnap = None if self.target_cheap else self.tcaches

        keys = self._next_rng(2 * B)
        active_dev = jnp.asarray(active)

        if sessions is not None:
            draft_fn, verify_fn = sessions
            dres = draft_fn(dbundle.params, dcaches_in,
                            jnp.asarray(in_toks), jnp.asarray(arm_mat),
                            jnp.float32(self.controller.lam), keys[:B],
                            active_dev)
            vres = verify_fn(self.target.params, self.tcaches,
                             jnp.asarray(last_toks), dres.tokens,
                             dres.n_drafted, dres.qprobs, keys[B:],
                             active_dev)
        else:
            dres = draft_session_batched(
                dbundle.params, dbundle.cfg, dspec, dcaches_in,
                jnp.asarray(in_toks), arm_mat, jnp.float32(self.controller.lam),
                keys[:B], active_dev, arms=self.controller.arms, gamma_max=g,
                temperature=self.temperature, n_prompt_tokens=n_in)
            vres = verify_session_batched(
                self.target.params, self.target.cfg, self.tspec, self.tcaches,
                jnp.asarray(last_toks), dres.tokens, dres.n_drafted,
                dres.qprobs, keys[B:], active_dev, gamma_max=g,
                temperature=self.temperature, greedy=self.greedy)

        nd = np.asarray(dres.n_drafted)
        m = np.asarray(vres.n_accepted)
        out_all = np.asarray(vres.out_tokens)
        if self.collect_traces:
            sig_all = np.asarray(dres.signals)
            ent_all = np.asarray(dres.entropies)

        # ---- per-stream output assembly + accounting
        feeds = {}
        for s in act_idx:
            st = self.slots[s]
            seq, res = st["seq"], st["res"]
            out = out_all[s, :m[s] + 1].tolist()
            feeds[s] = np.asarray([seq[-1:] + out[:-1]], np.int32)
            seq.extend(out)
            arm = int(shape_idx) if rt else int(arm_mat[s, 0])
            res.sessions.append(SessionStats(int(nd[s]), int(m[s]), arm))
            res.modeled_cost += modeled_session_cost(
                int(nd[s]) + n_in - 1, c_d, c_t)
            if self.collect_traces:
                res.traces.append({
                    "signals": sig_all[s], "entropies": ent_all[s],
                    "n_drafted": int(nd[s]), "n_accepted": int(m[s]),
                    "position_base": 0})
            eos = st["eos_id"]
            if eos is not None and eos in out:
                seq[:] = seq[:len(seq) - len(out) + out.index(eos) + 1]
                st["done"] = True
            if len(seq) + g + 2 >= self.max_len:
                st["done"] = True

        # ---- batched cache maintenance
        def readvance(which, params, snap):
            # snapshot rollback: inactive lanes keep the pre-tick snapshot,
            # active lanes are re-advanced by their accepted tokens, and the
            # batch is restacked ONCE (not one full-tree copy per lane).
            # Drafter-pool re-advances go through the canonical chunk
            # schedule — {prefill_chunk, 1} feed shapes only — so a pool
            # drafter's whole serving surface compiles a FIXED set of
            # programs (the zero-retrace-after-warmup guarantee).
            lanes = []
            for s in range(B):
                lane = _tree_get_slot(snap, s)
                if active[s]:
                    if rt and which == "draft":
                        tag = f"draft:{rt['name']}"
                        for lo, hi in _chunk_schedule(feeds[s].shape[1],
                                                      self.prefill_chunk):
                            lane = self._advance_with(
                                tag, dbundle, dspec, lane, feeds[s][:, lo:hi])
                    else:
                        lane = self._advance(which, params, lane, feeds[s])
                lanes.append(lane)
            return jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)

        if self.target_cheap:
            self._tpos[:] = np.where(active, L + m, self._tpos)
            self.tcaches = rollback(vres.cache, self._tpos)
        else:
            self.tcaches = self._place_cache(
                readvance("target", self.target.params, tsnap), slots=True)
            self._tpos[:] = np.where(active, L + m, self._tpos)
        if dcheap:
            dpos_arr[:] = np.where(active, L + m - 1, dpos_arr)
            new_dcaches = rollback(dres.cache, dpos_arr)
        else:
            new_dcaches = self._place_cache(
                readvance("draft", dbundle.params, dsnap), slots=True)
            dpos_arr[:] = np.where(active, L + m, dpos_arr)
        if rt:
            self._set_dr_caches(rt["name"], new_dcaches)
        else:
            self.dcaches = new_dcaches

        # ---- one order-independent batched bandit update for the tick
        if rt:
            self.controller.update_shape_batch(shape_idx, nd[act_idx],
                                               m[act_idx])
        else:
            self.controller.update_batch(arm_mat[act_idx], nd[act_idx],
                                         m[act_idx])
        return act_idx.tolist()


# ===================================================================== paged

_POOL_KEYS = POOL_LEAF_KEYS


def _path_keys(path):
    return [getattr(p, "key", None) for p in path]


def _chunk_schedule(n_tokens: int, chunk: int) -> List[tuple]:
    """``(lo, hi)`` feed windows of a prefill: whole ``chunk``-token
    windows first, then singles for the unaligned tail.  ONE canonical
    schedule shared by monolithic and per-tick chunked prefill — same
    windows at the same offsets means the same compiled programs see the
    same operands, so the two paths stay bit-identical."""
    n_whole = n_tokens // chunk
    sched = [(i * chunk, (i + 1) * chunk) for i in range(n_whole)]
    sched += [(j, j + 1) for j in range(n_whole * chunk, n_tokens)]
    return sched


class PagedSpecEngine(_ShardingMixin):
    """Paged slot engine: B streams share global KV block pools.

    Where ``BatchedSpecEngine`` stacks one dense ``max_len`` cache per slot
    (memory = B x max_len x layers whether or not a stream uses it), this
    engine owns ONE block pool per attention layer plus per-stream block
    tables and lengths (``models/cache.py``).  Consequences:

      * pool memory is sized by ``pool_tokens`` — independent of both B and
        ``max_len`` — so concurrency is no longer capped by the dense
        worst-case allocation;
      * rollback after a tick is ONE per-stream length truncation for every
        attention/MLA layer at once (``paged_rollback``) — no per-kind
        special cases (recurrent layers keep snapshot-recompute, which the
        paged layout leaves untouched);
      * admission reserves physical blocks for a request's worst case
        (prompt + budget + draft overshoot) up front, so a running stream
        can never hit pool exhaustion mid-flight; ``can_admit`` lets the
        scheduler backpressure instead of admitting.

    The batched draft/verify programs are BATCH-NATIVE (not vmapped — the
    shared pool forbids per-lane functional writes) and compile once per
    (B, gamma_max); admission/release only change table/length DATA, never
    shapes, so a request joining the running batch never recompiles.
    Masked lanes write into the reserved trash block 0.

    ``fused=True`` (default, cheap-rollback stacks only) collapses the tick
    into one device program with the launch/flush split — identical
    semantics to ``BatchedSpecEngine``'s, with per-lane LENGTH truncation
    standing in for the dense pointer rollback.
    """

    backend_name = "paged"

    def __init__(self, draft: ModelBundle, target: ModelBundle,
                 controller: Controller, *, batch_size: int = 4,
                 max_len: int = 2048, block_size: int = 64,
                 pool_tokens: Optional[int] = None,
                 temperature: float = 0.0, greedy: bool = True,
                 cache_dtype=jnp.float32, kv_dtype: Optional[str] = None,
                 quant_draft: bool = False, seed: int = 0,
                 prefill_chunk: int = 16, fused: bool = True,
                 prefix_cache: bool = False, mesh=None):
        assert batch_size >= 1
        if quant_draft:
            draft = quantized_bundle(draft)
        self.draft, self.target = draft, target
        self.mesh = mesh
        self._place_bundles()
        self.controller = controller
        self.gamma_max = controller.gamma_max
        self.batch_size = batch_size
        self.max_len = max_len
        self.block_size = block_size
        self.pool_tokens = pool_tokens or batch_size * max_len
        self.temperature = temperature
        self.greedy = greedy
        self.cache_dtype = cache_dtype
        self.kv_dtype = kv_dtype
        self.prefill_chunk = prefill_chunk
        self.rng = jax.random.PRNGKey(seed)
        self.collect_traces = False
        self._step_cache: Dict[tuple, callable] = {}

        B = batch_size
        self.dcache, self.dspec = T.init_paged_cache(
            draft.cfg, B, max_len, block_size=block_size,
            pool_tokens=self.pool_tokens, dtype=cache_dtype,
            kv_dtype=kv_dtype)
        self.tcache, self.tspec = T.init_paged_cache(
            target.cfg, B, max_len, block_size=block_size,
            pool_tokens=self.pool_tokens, dtype=cache_dtype,
            kv_dtype=kv_dtype, enc_segments=B + 1)
        # enc-dec targets: one host-side refcounted directory over the
        # shared encoder segment pools in tcache["cross"] — admission with
        # an already-seen encoding adopts its segment (zero encoder
        # compute, zero extra bytes), mirroring a prefix-cache hit
        self.enc_pool: Optional[EncoderSegmentPool] = (
            EncoderSegmentPool(B + 1) if target.cfg.is_encdec else None)
        # pools shard KV heads over "model" (whole block axis per shard —
        # any table may point anywhere); tables/lengths ride the lane axes
        self.dcache = self._place_cache(self.dcache, paged=True)
        self.tcache = self._place_cache(self.tcache, paged=True)
        self.draft_cheap = self.dspec.cheap_rollback
        self.target_cheap = self.tspec.cheap_rollback
        self.dalloc = BlockAllocator(self.dspec.num_blocks,
                                     self.dspec.max_blocks, B)
        self.talloc = BlockAllocator(self.tspec.num_blocks,
                                     self.tspec.max_blocks, B)
        # prefix-sharing admission (docs/prefix_sharing.md): hashed
        # block-aligned prompt chunks -> physical block runs in BOTH pools.
        # Adoption rewires tables/lengths, so it needs the attention/MLA-only
        # stacks whose per-stream state IS the pool (recurrent conv/ssm state
        # is integrated per stream and cannot be adopted from a block run).
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_cache:
            if not (self.draft_cheap and self.target_cheap):
                raise ValueError(
                    "prefix_cache=True needs attention/MLA-only stacks; "
                    "recurrent per-stream state cannot be block-shared")
            self.prefix_cache = PrefixCache(block_size,
                                            (self.dalloc, self.talloc))
        self.prefill_tokens_computed = 0
        self.prefill_tokens_skipped = 0
        self.cow_copies = 0
        self.preemptions = 0
        self.resumes = 0
        self._sharded_sessions = None
        if mesh is not None:
            from repro.launch.shardings import paged_cache_shardings
            self._sharded_sessions = make_sharded_sessions(
                mesh, cfg_d=self.draft.cfg, cfg_t=self.target.cfg,
                dspec=self.dspec, tspec=self.tspec,
                dparams_sh=self._dparams_sh, tparams_sh=self._tparams_sh,
                dcache_sh=paged_cache_shardings(mesh, self.dcache),
                tcache_sh=paged_cache_shardings(mesh, self.tcache),
                batch_size=batch_size, gamma_max=self.gamma_max,
                arms=controller.arms, temperature=temperature, greedy=greedy,
                n_prompt_tokens=2 if self.draft_cheap else 1, paged=True)

        self.fused = bool(fused and self.draft_cheap and self.target_cheap)
        self._fused_tick = None
        if self.fused:
            if mesh is None:
                self._fused_tick = self._meshless_fused(paged=True)
            else:
                from repro.launch.shardings import paged_cache_shardings
                self._fused_tick = make_sharded_fused(
                    mesh, cfg_d=self.draft.cfg, cfg_t=self.target.cfg,
                    dspec=self.dspec, tspec=self.tspec,
                    dparams_sh=self._dparams_sh, tparams_sh=self._tparams_sh,
                    dcache_sh=paged_cache_shardings(mesh, self.dcache),
                    tcache_sh=paged_cache_shardings(mesh, self.tcache),
                    batch_size=batch_size, gamma_max=self.gamma_max,
                    arms=controller.arms, temperature=temperature,
                    greedy=greedy, n_prompt_tokens=2, paged=True)

        self.slots: List[Optional[dict]] = [None] * B
        self._pending: Optional[dict] = None
        self._dlen = np.zeros(B, np.int64)   # host mirrors of device lengths
        self._tlen = np.zeros(B, np.int64)
        # per-slot TARGET position offset: P prepended patch positions for
        # vision-conditioned streams (lengths invariant becomes
        # len(seq) - 1 + toff).  Any nonzero offset forces the sync tick —
        # the fused program serves both models' rollbacks from ONE shared
        # lengths vector, which an asymmetric offset would break.
        self._toff = np.zeros(B, np.int64)
        self._init_moe_accounting()

    # -------------------------------------------------------- plumbing
    def _next_rng(self, n: int = 1):
        keys = jax.random.split(self.rng, n + 1)
        self.rng = keys[0]
        return keys[1:]

    def _jit_paged_step(self, which: str):
        # one wrapper per model; jax.jit specializes it per token shape
        if which not in self._step_cache:
            bundle = self.draft if which == "draft" else self.target
            spec = self.dspec if which == "draft" else self.tspec

            @jax.jit
            def fn(params, tokens, cache):
                return T.paged_step(params, bundle.cfg, tokens, cache, spec)
            self._step_cache[which] = fn
        return self._step_cache[which]

    def _lane_view(self, cache, slot: int):
        """Single-lane view: pools stay global, per-stream leaves sliced.
        Encoder segment pools ride whole (shared, indexed by the lane's
        ``cross_seg`` row) so a lane prefill is conditioned exactly like
        the batch-native tick; ``moe_stats`` is sliced per stream."""
        def f(path, a):
            keys = _path_keys(path)
            if keys[-1] in _POOL_KEYS:
                return a
            ax = 1 if keys[0] == "stack" else 0
            return jax.lax.slice_in_dim(a, slot, slot + 1, axis=ax)
        layers = jax.tree_util.tree_map_with_path(f, cache["layers"])
        lane = {"lengths": cache["lengths"][slot:slot + 1],
                "tables": cache["tables"][slot:slot + 1], "layers": layers}
        if "cross" in cache:
            lane["cross"] = cache["cross"]
            lane["cross_seg"] = cache["cross_seg"][slot:slot + 1]
        if "moe_stats" in cache:
            lane["moe_stats"] = cache["moe_stats"][slot:slot + 1]
        return lane

    def _merge_lane(self, cache, lane, slot: int):
        """Fold a lane view back: pools replace wholesale (the lane program
        updated them in place), per-stream leaves write lane ``slot``."""
        def f(path, big, one):
            keys = _path_keys(path)
            if keys[-1] in _POOL_KEYS:
                return one
            ax = 1 if keys[0] == "stack" else 0
            return jax.lax.dynamic_update_slice_in_dim(
                big, one.astype(big.dtype), slot, axis=ax)
        layers = jax.tree_util.tree_map_with_path(f, cache["layers"],
                                                  lane["layers"])
        return {**cache,
                "lengths": cache["lengths"].at[slot].set(lane["lengths"][0]),
                "layers": layers}

    def _advance_lane(self, which: str, cache, slot: int,
                      tokens: np.ndarray):
        """Feed ``tokens`` (1, L) through lane ``slot`` against the pool."""
        if tokens.shape[1] == 0:
            return cache
        bundle = self.draft if which == "draft" else self.target
        fn = self._jit_paged_step(which)
        lane = self._lane_view(cache, slot)
        _, lane = fn(bundle.params, jnp.asarray(tokens, jnp.int32), lane)
        return self._merge_lane(cache, lane, slot)

    def _reset_lane_state(self, cache, slot: int):
        """Zero lane ``slot``'s PER-STREAM leaves (recurrent conv/ssm/rec
        state).  Pools need no reset — a reused slot's stale rows are dead
        under the ``p < length`` mask — but recurrent state is integrated,
        not indexed, so a reused slot would otherwise prefill on top of the
        previous stream's final hidden state."""
        def f(path, a):
            keys = _path_keys(path)
            if keys[-1] in _POOL_KEYS:
                return a
            ax = 1 if keys[0] == "stack" else 0
            zeros = jnp.zeros_like(jax.lax.slice_in_dim(a, slot, slot + 1,
                                                        axis=ax))
            return jax.lax.dynamic_update_slice_in_dim(a, zeros, slot, axis=ax)
        return {**cache, "layers": jax.tree_util.tree_map_with_path(
            f, cache["layers"])}

    def _chunk_feed_lane(self, which: str, cache, slot: int,
                         tokens: np.ndarray, n_valid: int):
        """One resumable chunk-prefill step on lane ``slot``: feed a (1, C)
        buffer through ``chunk_prefill_paged`` (positions come from the
        lane's live length, so it resumes anywhere) and fold the lane back
        into the pool."""
        bundle = self.draft if which == "draft" else self.target
        spec = self.dspec if which == "draft" else self.tspec
        with TraceAnnotation("engine.prefill_chunk",
                             model=int(which == "target"), tokens=n_valid):
            lane = self._lane_view(cache, slot)
            lane = chunk_prefill_paged(bundle.params, bundle.cfg, spec, lane,
                                       jnp.asarray(tokens, jnp.int32), n_valid)
            return self._merge_lane(cache, lane, slot)

    def _prefill_lane(self, which: str, cache, slot: int, tokens: List[int]):
        """Monolithic prefill = the FULL chunk schedule run back to back.
        Routing it through the same ``chunk_prefill_paged`` program (and
        the same whole-chunks-then-singles schedule) that ``prefill_step``
        uses makes chunked and monolithic prefill bit-identical by
        construction — there is only one prefill program."""
        toks = np.asarray(tokens, np.int32)[None]
        for lo, hi in _chunk_schedule(toks.shape[1], self.prefill_chunk):
            cache = self._chunk_feed_lane(which, cache, slot,
                                          toks[:, lo:hi], hi - lo)
        return cache

    def _prefill_vlm_lane(self, slot: int, tokens: List[int], patch_embeds):
        """Conditioned target prefill: ONE feed of the projected patches +
        the whole prompt through the lane (positions come from the lane's
        zeroed length, so patches land at 0..P-1 and text at P.. with the
        right RoPE — same layout as the dense conditioned reference)."""
        lane = self._lane_view(self.tcache, slot)
        toks = jnp.asarray(np.asarray(tokens, np.int32)[None])
        _, lane = T.paged_step(self.target.params, self.target.cfg, toks,
                               lane, self.tspec,
                               patch_embeds=jnp.asarray(patch_embeds))
        return self._place_cache(self._merge_lane(self.tcache, lane, slot),
                                 paged=True)

    def _enc_seg_bytes(self) -> int:
        """Bytes ONE encoder segment occupies across every cross-KV pool."""
        cp = self.tcache["cross"]
        total = 0
        for c in cp["prefix"] + cp["tail"]:
            for a in jax.tree_util.tree_leaves(c):
                total += int(np.prod(a.shape[1:])) * a.dtype.itemsize
        if cp["stack"] is not None:
            for a in jax.tree_util.tree_leaves(cp["stack"]):
                total += int(a.shape[0] * np.prod(a.shape[2:])) * a.dtype.itemsize
        return total

    def _adopt_encoder_segment(self, slot: int, frame_embeds) -> int:
        """Admission half of encoder conditioning: digest the raw frames,
        adopt the cached segment on a hit (refcount bump — no encoder
        forward, no new pool rows), else encode ONCE into a free segment.
        Either way the slot's ``cross_seg`` row points at it afterwards."""
        fe = np.asarray(frame_embeds, np.float32)
        if fe.ndim == 2:
            fe = fe[None]
        seg, is_new = self.enc_pool.acquire(EncoderSegmentPool.digest(fe),
                                            self._enc_seg_bytes())
        if is_new:
            cross_lane = T.encode_cross_segment(self.target.params,
                                                self.target.cfg,
                                                jnp.asarray(fe))
            self.tcache = T.write_cross_segment(self.tcache, cross_lane, seg)
        self.tcache = self._place_cache(
            {**self.tcache,
             "cross_seg": self.tcache["cross_seg"].at[slot].set(seg)},
            paged=True)
        return seg

    # -------------------------------------------------------- slots
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def active_mask(self) -> np.ndarray:
        """Slots that decode THIS tick.  A slot still mid-chunked-prefill
        occupies its lane and blocks but rides the tick masked (its lane's
        garbage feed lands in its own reserved pages past the length
        mirror, dead under the tick's rollback and overwritten by the next
        real prefill chunk) until ``prefill_step`` finishes the prompt."""
        return np.array([s is not None and not s["done"]
                         and not s.get("prefilling") for s in self.slots])

    def prefilling_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots)
                if s is not None and s.get("prefilling")]

    def reserve_blocks_for(self, reserve_tokens: int) -> int:
        """Physical blocks a request with this worst-case length needs."""
        need = min(reserve_tokens, self.max_len)
        return self.dalloc.blocks_for(need, self.block_size)

    def _adoptable(self, prompt: List[int], touch: bool = False):
        """(n_adopt, runs, n_cow): the longest cached chunk run inside the
        prompt's prefill region [0, P-1), and whether adopting it forces a
        copy-on-write of the draft's frontier block.

        The draft refeeds from position P-2, so an adopted block containing
        P-2 (only possible when the run ends EXACTLY at P-1, i.e. ``bs``
        divides P-1) must be privatized before the first tick; the target
        writes from P-1, which by construction lies past every adopted
        block, so it never needs one."""
        if self.prefix_cache is None or len(prompt) < 2:
            return 0, None, 0
        n, runs = self.prefix_cache.match(prompt, limit_tokens=len(prompt) - 1,
                                          touch=touch)
        n_cow = 1 if n and (len(prompt) - 2) // self.block_size < n else 0
        return n, runs, n_cow

    def can_admit(self, reserve_tokens: int,
                  prompt: Optional[List[int]] = None) -> bool:
        """Feasibility probe for the scheduler: with ``prompt`` given, a
        prefix-cache hit only needs the NON-SHARED suffix (plus at most one
        COW block), and evictable cached chunks count as reclaimable."""
        need = self.reserve_blocks_for(reserve_tokens)
        if not self.free_slots():
            return False
        n_adopt, _, n_cow = self._adoptable(prompt) if prompt else (0, None, 0)
        evictable = (self.prefix_cache.evictable_chunks()
                     if self.prefix_cache is not None else 0)
        # the adopted run is refcount==1 until admission pins it, so it is
        # counted inside ``evictable_chunks`` — subtract it (floored at 0)
        # or capacity is overstated by up to ``n_adopt`` blocks per pool
        evictable = max(evictable - n_adopt, 0)
        need_new = max(need - n_adopt, 0) + n_cow
        return all(need_new <= len(a.free) + evictable
                   for a in (self.dalloc, self.talloc))

    @_on_mesh
    def open_stream(self, slot: int, prompt: List[int],
                    eos_id: Optional[int] = None,
                    reserve_tokens: Optional[int] = None,
                    resume_from: Optional[GenResult] = None, *,
                    frame_embeds=None, patch_embeds=None) -> dict:
        """Admit a stream: reserve blocks, prefill the prompt into its pages.

        ``reserve_tokens`` is the worst-case sequence length this request
        can reach (prompt + new-token budget + gamma slack); default is
        ``max_len`` (dense-equivalent reservation).  Raises
        ``PoolExhausted`` when the pool cannot cover it — callers should
        check ``can_admit`` first and backpressure.

        With a ``PrefixCache``, admission first matches the prompt's
        block-aligned chunks: adopted blocks are SHARED (table row aliases,
        refcount bumps, zero prefill compute), only the non-shared suffix
        is reserved privately, the draft's frontier block is copied-on-write
        if the adopted run reaches it, and after prefill the stream's own
        full blocks below its write frontier are registered for the next
        stream to adopt.

        ``resume_from`` re-opens a PREEMPTED stream from the handle
        ``preempt_stream`` returned: pass the frozen sequence as
        ``prompt`` and the frozen ``res`` here — accounting continues on
        the same ``GenResult``, and the blocks ``preempt_stream``
        registered make the re-prefill a prefix-cache adoption.

        Conditioning (target-side; draft stays a text-only decoder):
        ``frame_embeds`` (T, frontend_dim) for enc-dec targets lands as a
        SHARED, refcounted encoder segment — admission with an
        already-cached encoding adopts the segment exactly like a
        prefix-cache hit (zero encoder compute, zero extra pool bytes);
        ``patch_embeds`` (P, vit_dim) for vision targets prepends P patch
        positions, offsetting the target lane's lengths by P.  Conditioned
        streams skip prefix-cache adoption/registration (their KV depends
        on the conditioning, not only on the token prefix)."""
        assert self.slots[slot] is None, f"slot {slot} busy"
        assert len(prompt) >= 2, "need >= 2 prompt tokens"
        cond = frame_embeds is not None or patch_embeds is not None
        toff = 0
        if patch_embeds is not None:
            patch_embeds = np.asarray(patch_embeds)
            if patch_embeds.ndim == 2:
                patch_embeds = patch_embeds[None]
            toff = int(patch_embeds.shape[1])
            assert self.target_cheap, \
                "patch conditioning needs an attention/MLA-only target"
            if reserve_tokens is not None:
                reserve_tokens += toff       # patches occupy pool positions
        assert len(prompt) + self.gamma_max + 2 + toff <= self.max_len, \
            "prompt cannot fit a single session within max_len"
        pre = prompt[:-1]                    # invariant: length = len(seq) - 1
        adopted = self._admit_blocks(slot, prompt, reserve_tokens,
                                     use_prefix=not cond)
        rest = pre[adopted:]
        self.prefill_tokens_skipped += adopted
        self.prefill_tokens_computed += len(rest)
        enc_seg = None
        if frame_embeds is not None:
            assert self.enc_pool is not None, \
                "frame_embeds needs an enc-dec target"
            enc_seg = self._adopt_encoder_segment(slot, frame_embeds)
        self.dcache = self._place_cache(
            self._prefill_lane("draft", self.dcache, slot, rest), paged=True)
        if patch_embeds is not None:
            self.tcache = self._prefill_vlm_lane(slot, rest, patch_embeds)
        else:
            self.tcache = self._place_cache(
                self._prefill_lane("target", self.tcache, slot, rest),
                paged=True)
        self._dlen[slot] = len(pre)
        self._tlen[slot] = len(pre) + toff
        self._toff[slot] = toff
        st = self._new_stream_state(slot, prompt, eos_id, resume_from)
        st["cond"] = cond
        st["enc_seg"] = enc_seg
        self._register_prefix(slot)
        return st

    @_on_mesh
    def open_stream_chunked(self, slot: int, prompt: List[int],
                            eos_id: Optional[int] = None,
                            reserve_tokens: Optional[int] = None,
                            resume_from: Optional[GenResult] = None) -> dict:
        """``open_stream`` that RESERVES but does not prefill: blocks (and
        any prefix-cache adoption) happen now, the prompt's non-shared
        suffix is fed later in bounded per-tick chunks via
        ``prefill_step``.  Until the prompt is fully fed the slot is
        occupied but inactive (``active_mask`` excludes it), so in-flight
        decode ticks never stall behind a long admission prefill."""
        assert self.slots[slot] is None, f"slot {slot} busy"
        assert len(prompt) >= 2, "need >= 2 prompt tokens"
        assert len(prompt) + self.gamma_max + 2 <= self.max_len, \
            "prompt cannot fit a single session within max_len"
        pre = prompt[:-1]
        adopted = self._admit_blocks(slot, prompt, reserve_tokens)
        self.prefill_tokens_skipped += adopted
        self._dlen[slot] = adopted
        self._tlen[slot] = adopted
        st = self._new_stream_state(slot, prompt, eos_id, resume_from)
        if adopted >= len(pre):              # full prefix hit: nothing to feed
            self._dlen[slot] = len(pre)
            self._tlen[slot] = len(pre)
            self.dcache = {**self.dcache, "lengths":
                           self.dcache["lengths"].at[slot].set(len(pre))}
            self.tcache = {**self.tcache, "lengths":
                           self.tcache["lengths"].at[slot].set(len(pre))}
            self._register_prefix(slot)
            return st
        st["prefilling"] = True
        st["prefill_rest"] = pre[adopted:]
        st["prefill_pos"] = 0
        return st

    @_on_mesh
    def prefill_step(self, slot: int,
                     max_tokens: Optional[int] = None) -> int:
        """Feed up to ``max_tokens`` more prompt tokens into a slot opened
        by ``open_stream_chunked`` (at least one schedule window makes
        progress even when the budget is smaller).  Follows the SAME
        whole-chunks-then-singles schedule as monolithic prefill, so a
        prompt fed over many ticks lands bit-identical KV.  Returns the
        tokens fed; on the last chunk the slot flips active and registers
        its prefix-cache blocks."""
        st = self.slots[slot]
        assert st is not None and st.get("prefilling"), \
            f"slot {slot} is not mid-prefill"
        rest, pos = st["prefill_rest"], st["prefill_pos"]
        budget = len(rest) - pos if max_tokens is None else max_tokens
        fed = 0
        for lo, hi in _chunk_schedule(len(rest), self.prefill_chunk):
            if hi <= pos:                    # fed in an earlier call
                continue
            if fed and fed + (hi - lo) > budget:
                break
            toks = np.asarray(rest[lo:hi], np.int32)[None]
            self.dcache = self._chunk_feed_lane("draft", self.dcache, slot,
                                                toks, hi - lo)
            self.tcache = self._chunk_feed_lane("target", self.tcache, slot,
                                                toks, hi - lo)
            fed += hi - lo
            pos = hi
        st["prefill_pos"] = pos
        self._dlen[slot] += fed
        self._tlen[slot] += fed
        self.prefill_tokens_computed += fed
        self.dcache = self._place_cache(self.dcache, paged=True)
        self.tcache = self._place_cache(self.tcache, paged=True)
        if pos >= len(rest):
            st["prefilling"] = False
            del st["prefill_rest"], st["prefill_pos"]
            self._register_prefix(slot)
        return fed

    def preempt_stream(self, slot: int) -> dict:
        """Evict a running (or mid-prefill) stream and return a frozen
        handle for later resume.  O(1) per block: the stream's full blocks
        below its write frontier are registered in the prefix cache FIRST
        (refcount keeps them warm across the release), so resuming via
        ``open_stream(frozen["seq"], resume_from=frozen["res"])`` adopts
        the KV computed so far instead of recomputing it — at most the
        sub-block frontier tail is re-prefilled.  The pending tick must be
        flushed first (preemption between flush and launch)."""
        assert self._pending is None, "flush the pending tick before preempt"
        st = self.slots[slot]
        assert st is not None, f"slot {slot} empty"
        assert not st.get("cond"), \
            "conditioned streams cannot be preempted (the resume handle " \
            "carries tokens only, not the conditioning)"
        self._register_prefix(slot)
        self.preemptions += 1
        frozen = self.close_stream(slot)
        return {"seq": list(frozen["seq"]), "res": frozen["res"],
                "eos_id": frozen["eos_id"]}

    def _new_stream_state(self, slot: int, prompt: List[int],
                          eos_id: Optional[int],
                          resume_from: Optional[GenResult]) -> dict:
        seq = list(prompt)
        if resume_from is not None:
            res = resume_from
            res.tokens = seq                 # res tracks the live seq again
            self.resumes += 1
        else:
            res = GenResult(tokens=seq, prompt_len=len(prompt))
        st = {"seq": seq, "res": res, "done": False, "eos_id": eos_id}
        self.slots[slot] = st
        return st

    def _register_prefix(self, slot: int) -> None:
        """Register ``slot``'s full blocks strictly below its draft write
        frontier (positions the stream can never rewrite, so the cached KV
        stays bit-exact for the blocks' whole cache lifetime).  At rest
        the frontier is ``len(seq) - 2``; mid-prefill it is the prefill
        position, whichever is lower."""
        if self.prefix_cache is None or self.slots[slot].get("cond"):
            return
        seq = self.slots[slot]["seq"]
        upto = min(int(self._dlen[slot]), len(seq) - 2)
        n_reg = upto // self.block_size
        if n_reg > 0:
            self.prefix_cache.insert(
                seq, n_reg,
                (self.dalloc.owned[slot], self.talloc.owned[slot]))

    def _admit_blocks(self, slot: int, prompt: List[int],
                      reserve_tokens: Optional[int], *,
                      use_prefix: bool = True) -> int:
        """Block-reservation half of admission: adopt what the prefix
        cache holds, evict/allocate the rest, point the slot's tables at
        the run, privatize the draft's COW frontier.  Returns the adopted
        token count (device lengths are set to it; the caller prefills
        ``prompt[adopted:-1]``).  ``use_prefix=False`` (conditioned
        streams) skips adoption — their KV is not a pure token function."""
        need = self.reserve_blocks_for(reserve_tokens or self.max_len)
        seq = list(prompt)
        n_adopt, runs, n_cow = (self._adoptable(prompt, touch=True)
                                if use_prefix else (0, None, 0))
        need = max(need, n_adopt)
        need_new = need - n_adopt + n_cow
        # Pin the adopted run BEFORE any eviction: until ``share`` runs the
        # matched chunks are refcount==1 (cache-owned only), so a
        # deficit-driven evict could free the very blocks being adopted.
        # The pin also takes them out of ``evictable_chunks`` below, so the
        # feasibility check cannot count on reclaiming them.
        if n_adopt:
            for alloc, run in zip((self.dalloc, self.talloc), runs):
                for b in run[:n_adopt]:
                    alloc.addref(int(b))
        try:
            deficit = max(need_new - len(self.dalloc.free),
                          need_new - len(self.talloc.free))
            if deficit > 0:
                evictable = (self.prefix_cache.evictable_chunks()
                             if self.prefix_cache is not None else 0)
                if deficit > evictable:
                    # doomed admission: backpressure WITHOUT flushing warm
                    # prefixes the request cannot use anyway
                    raise PoolExhausted(
                        f"{need_new} blocks unavailable for admission "
                        f"({deficit - evictable} short after eviction)")
                self.prefix_cache.evict(deficit)
            if not (self.dalloc.can_allocate(need_new)
                    and self.talloc.can_allocate(need_new)):
                raise PoolExhausted(
                    f"{need_new} blocks unavailable for admission")
            if n_adopt:
                self.dalloc.share(slot, runs[0][:n_adopt])
                self.talloc.share(slot, runs[1][:n_adopt])
                self.dalloc.extend(slot, need - n_adopt)
                self.talloc.extend(slot, need - n_adopt)
            else:
                self.dalloc.allocate(slot, need)
                self.talloc.allocate(slot, need)
        finally:
            # drop the admission pin: the cache ref (and, on success, the
            # stream's ``share`` ref) keep the blocks alive
            if n_adopt:
                for alloc, run in zip((self.dalloc, self.talloc), runs):
                    for b in run[:n_adopt]:
                        alloc.decref(int(b))
        adopted = n_adopt * self.block_size
        self.dcache = {**self.dcache,
                       "tables": jnp.asarray(self.dalloc.tables),
                       "lengths": self.dcache["lengths"].at[slot].set(adopted)}
        self.tcache = {**self.tcache,
                       "tables": jnp.asarray(self.talloc.tables),
                       "lengths": self.tcache["lengths"].at[slot].set(adopted)}
        if not self.draft_cheap:
            self.dcache = self._reset_lane_state(self.dcache, slot)
        if not self.target_cheap:
            self.tcache = self._reset_lane_state(self.tcache, slot)
        if n_adopt:
            # copy-on-first-divergent-write: privatize any adopted block the
            # stream will write into (draft refeeds from P-2, target from
            # P-1 — at most the draft's one frontier block, see _adoptable)
            self.dcache = self._cow_frontier("draft", slot, len(seq) - 2)
            self.tcache = self._cow_frontier("target", slot, len(seq) - 1)
        return adopted

    def _cow_frontier(self, which: str, slot: int, first_write_pos: int):
        """Privatize every non-writable block of ``slot`` that overlaps the
        write range ``[first_write_pos, ...)``: allocate a fresh block, copy
        the shared block's pool rows (all leaves, int8 scales included),
        repoint the table row, drop the shared reference."""
        alloc = self.dalloc if which == "draft" else self.talloc
        cache = self.dcache if which == "draft" else self.tcache
        copied = False
        start = max(first_write_pos, 0) // self.block_size
        for idx in range(start, len(alloc.owned[slot])):
            if not alloc.writable(slot, idx):
                src, dst = alloc.cow(slot, idx)
                cache = paged_copy_block(cache, src, dst)
                self.cow_copies += 1
                copied = True
        if copied:
            cache = {**cache, "tables": jnp.asarray(alloc.tables)}
        return cache

    def _assert_cow_safety(self) -> None:
        """Every active lane's write range THIS TICK (draft from L-2,
        target from L-1, at most gamma_max tokens ahead) must sit in
        sole-owner, non-immutable blocks — speculative writes and rollback
        can then never touch a block another stream or the cache still
        references.  Only the tick's write window is checked (a handful of
        blocks per lane, not the whole reservation): blocks past it are
        fresh private extends that nothing can alias before the frontier
        reaches them, and checking them every launch made this O(slots x
        owned_blocks) host work in the serving hot path."""
        bs = self.block_size
        for s in np.flatnonzero(self.active_mask()):
            L = len(self.slots[int(s)]["seq"])
            hi = (L + self.gamma_max) // bs       # last block written this tick
            for alloc, first in ((self.dalloc, L - 2), (self.talloc, L - 1)):
                owned = alloc.owned[int(s)]
                for idx in range(max(first, 0) // bs,
                                 min(len(owned), hi + 1)):
                    assert alloc.writable(int(s), idx), (
                        f"slot {s}: write-frontier block {owned[idx]} "
                        f"(logical {idx}) is shared/immutable — COW missed")

    def close_stream(self, slot: int) -> dict:
        """Release a slot: blocks return to the pool, its table row points
        at the trash block again (and any adopted encoder segment drops a
        reference — last release frees the segment for reuse)."""
        st = self.slots[slot]
        assert st is not None
        self.slots[slot] = None
        self.dalloc.release(slot)
        self.talloc.release(slot)
        self._dlen[slot] = 0
        self._tlen[slot] = 0
        self._toff[slot] = 0
        tcache = {**self.tcache, "tables": jnp.asarray(self.talloc.tables),
                  "lengths": self.tcache["lengths"].at[slot].set(0)}
        if st.get("enc_seg"):
            self.enc_pool.release(int(st["enc_seg"]))
            tcache["cross_seg"] = tcache["cross_seg"].at[slot].set(0)
        self.dcache = self._place_cache(
            {**self.dcache, "tables": jnp.asarray(self.dalloc.tables),
             "lengths": self.dcache["lengths"].at[slot].set(0)}, paged=True)
        self.tcache = self._place_cache(tcache, paged=True)
        return st

    # -------------------------------------------------------- tick
    def session_step_batch(self) -> List[int]:
        """One batched draft/verify session across every active slot
        (one synchronous tick: launch + flush back to back)."""
        self.session_step_launch()
        return self.session_step_flush()

    @_on_mesh
    def session_step_launch(self) -> bool:
        """Dispatch one tick without reading its outcomes back (see
        ``BatchedSpecEngine.session_step_launch`` — identical protocol,
        with per-lane length mirrors instead of pointer mirrors)."""
        assert self._pending is None, "previous tick not flushed"
        B, g = self.batch_size, self.gamma_max
        active = self.active_mask()
        act_idx = np.flatnonzero(active)
        if act_idx.size == 0:
            return False
        if __debug__ and self.prefix_cache is not None:
            self._assert_cow_safety()
        if not self.fused or self._toff.any():
            # offset streams (vision-conditioned lanes) take the sync tick:
            # the fused program rolls BOTH models back from one shared
            # lengths vector, which an asymmetric target offset would break
            self._pending = {"acted": self._session_step_sync()}
            return True

        L = np.array([len(self.slots[s]["seq"]) if self.slots[s] else 0
                      for s in range(B)], np.int64)
        arm_mat = np.zeros((B, g), np.int32)
        arm_mat[act_idx] = self.controller.begin_batch(act_idx.size)
        in_toks = np.zeros((B, 2), np.int32)
        last_toks = np.zeros((B, 1), np.int32)
        for s in act_idx:
            seq = self.slots[s]["seq"]
            in_toks[s] = seq[-2:]
            last_toks[s, 0] = seq[-1]
        with TraceAnnotation("engine.launch_dispatch"):
            keys = self._next_rng(2 * B)
            ft = self._fused_tick(
                self.draft.params, self.target.params, self.dcache,
                self.tcache, jnp.asarray(in_toks), jnp.asarray(last_toks),
                jnp.asarray(arm_mat), jnp.float32(self.controller.lam),
                keys[:B], keys[B:], jnp.asarray(active),
                jnp.asarray(L, jnp.int32),
                jnp.asarray(self._dlen, jnp.int32),
                jnp.asarray(self._tlen, jnp.int32))
        self.dcache, self.tcache = ft.dcache, ft.tcache
        self._pending = {"act_idx": act_idx, "active": active,
                         "arm_mat": arm_mat, "L": L, "ft": ft}
        return True

    @_on_mesh
    def session_step_flush(self) -> List[int]:
        """Host accounting for the pending tick + the bandit update."""
        pending, self._pending = self._pending, None
        if pending is None:
            return []
        if "acted" in pending:
            return pending["acted"]
        active, act_idx = pending["active"], pending["act_idx"]
        arm_mat, L, ft = pending["arm_mat"], pending["L"], pending["ft"]
        g = self.gamma_max
        c_d = self.draft.cost_per_token
        c_t = self.target.cost_per_token
        with TraceAnnotation("engine.flush_wait"):
            jax.block_until_ready((ft.n_drafted, ft.n_accepted,
                                   ft.out_tokens))
        nd = np.asarray(ft.n_drafted)
        m = np.asarray(ft.n_accepted)
        out_all = np.asarray(ft.out_tokens)
        dens = (self._routing_density_rows(self.tcache)
                if self._routed_frac > 0.0 else None)
        if self.collect_traces:
            sig_all = np.asarray(ft.signals)
            ent_all = np.asarray(ft.entropies)
        for s in act_idx:
            st = self.slots[s]
            seq, res = st["seq"], st["res"]
            out = out_all[s, :m[s] + 1].tolist()
            seq.extend(out)
            res.sessions.append(SessionStats(int(nd[s]), int(m[s]),
                                             int(arm_mat[s, 0])))
            density = 1.0
            if dens is not None:
                density = float(dens[s])
                self._moe_density_sum += density
                self._moe_sessions += 1
            res.modeled_cost += modeled_session_cost(
                int(nd[s]) + 1, c_d, c_t, routed_frac=self._routed_frac,
                routing_density=density)
            if self.collect_traces:
                res.traces.append({
                    "signals": sig_all[s], "entropies": ent_all[s],
                    "n_drafted": int(nd[s]), "n_accepted": int(m[s]),
                    "position_base": 0})
            eos = st["eos_id"]
            if eos is not None and eos in out:
                seq[:] = seq[:len(seq) - len(out) + out.index(eos) + 1]
                st["done"] = True
            if len(seq) + g + 2 >= self.max_len:
                st["done"] = True
        self._tlen = np.where(active, L + m, self._tlen)
        self._dlen = np.where(active, L + m - 1, self._dlen)
        self.controller.update_batch(arm_mat[act_idx], nd[act_idx], m[act_idx])
        return act_idx.tolist()

    def _session_step_sync(self) -> List[int]:
        """The classic two-dispatch tick (recurrent stacks only)."""
        B, g = self.batch_size, self.gamma_max
        active = self.active_mask()
        act_idx = np.flatnonzero(active)
        if act_idx.size == 0:
            return []
        c_d = self.draft.cost_per_token
        c_t = self.target.cost_per_token
        L = np.array([len(self.slots[s]["seq"]) if self.slots[s] else 0
                      for s in range(B)], np.int64)

        arm_mat = np.zeros((B, g), np.int32)
        arm_mat[act_idx] = self.controller.begin_batch(act_idx.size)

        n_in = 2 if self.draft_cheap else 1
        in_toks = np.zeros((B, n_in), np.int32)
        last_toks = np.zeros((B, 1), np.int32)
        for s in act_idx:
            seq = self.slots[s]["seq"]
            in_toks[s] = seq[-n_in:]
            last_toks[s, 0] = seq[-1]

        if self.draft_cheap:
            # O(1) paged rollback INTO the session: truncate each active
            # lane to L-2 and refeed the last two tokens (same invariant
            # as the dense pointer-rollback path)
            dlen_in = np.where(active, L - 2, self._dlen)
            dcache_in = paged_rollback(self.dcache, dlen_in)
            dsnap = None
        else:
            dsnap = self.dcache
            dcache_in = self.dcache
        tsnap = None if self.target_cheap else self.tcache

        keys = self._next_rng(2 * B)
        active_dev = jnp.asarray(active)

        if self._sharded_sessions is not None:
            draft_fn, verify_fn = self._sharded_sessions
            dres = draft_fn(self.draft.params, dcache_in,
                            jnp.asarray(in_toks), jnp.asarray(arm_mat),
                            jnp.float32(self.controller.lam), keys[:B],
                            active_dev)
            vres = verify_fn(self.target.params, self.tcache,
                             jnp.asarray(last_toks), dres.tokens,
                             dres.n_drafted, dres.qprobs, keys[B:],
                             active_dev)
        else:
            dres = draft_session_paged(
                self.draft.params, self.draft.cfg, self.dspec, dcache_in,
                jnp.asarray(in_toks), jnp.asarray(arm_mat),
                jnp.float32(self.controller.lam), keys[:B], active_dev,
                arms=self.controller.arms, gamma_max=g,
                temperature=self.temperature, n_prompt_tokens=n_in)
            vres = verify_session_paged(
                self.target.params, self.target.cfg, self.tspec, self.tcache,
                jnp.asarray(last_toks), dres.tokens, dres.n_drafted,
                dres.qprobs, keys[B:], active_dev, gamma_max=g,
                temperature=self.temperature, greedy=self.greedy)

        nd = np.asarray(dres.n_drafted)
        m = np.asarray(vres.n_accepted)
        out_all = np.asarray(vres.out_tokens)
        dens = (self._routing_density_rows(vres.cache)
                if self._routed_frac > 0.0 else None)
        if self.collect_traces:
            sig_all = np.asarray(dres.signals)
            ent_all = np.asarray(dres.entropies)

        feeds = {}
        for s in act_idx:
            st = self.slots[s]
            seq, res = st["seq"], st["res"]
            out = out_all[s, :m[s] + 1].tolist()
            feeds[s] = np.asarray([seq[-1:] + out[:-1]], np.int32)
            seq.extend(out)
            res.sessions.append(SessionStats(int(nd[s]), int(m[s]),
                                             int(arm_mat[s, 0])))
            density = 1.0
            if dens is not None:
                density = float(dens[s])
                self._moe_density_sum += density
                self._moe_sessions += 1
            res.modeled_cost += modeled_session_cost(
                int(nd[s]) + n_in - 1, c_d, c_t,
                routed_frac=self._routed_frac, routing_density=density)
            if self.collect_traces:
                res.traces.append({
                    "signals": sig_all[s], "entropies": ent_all[s],
                    "n_drafted": int(nd[s]), "n_accepted": int(m[s]),
                    "position_base": 0})
            eos = st["eos_id"]
            if eos is not None and eos in out:
                seq[:] = seq[:len(seq) - len(out) + out.index(eos) + 1]
                st["done"] = True
            if len(seq) + g + 2 + int(self._toff[s]) >= self.max_len:
                st["done"] = True

        # ---- rollback: ONE length truncation per model (all layer kinds);
        # the target's truncation carries each lane's position offset
        if self.target_cheap:
            self._tlen = np.where(active, L + m + self._toff, self._tlen)
            self.tcache = paged_rollback(vres.cache, self._tlen)
        else:
            self.tcache = self._place_cache(
                self._readvance("target", tsnap, active, feeds), paged=True)
            self._tlen = np.where(active, L + m, self._tlen)
        if self.draft_cheap:
            self._dlen = np.where(active, L + m - 1, self._dlen)
            self.dcache = paged_rollback(dres.cache, self._dlen)
        else:
            self.dcache = self._place_cache(
                self._readvance("draft", dsnap, active, feeds), paged=True)
            self._dlen = np.where(active, L + m, self._dlen)

        self.controller.update_batch(arm_mat[act_idx], nd[act_idx], m[act_idx])
        return act_idx.tolist()

    def _readvance(self, which: str, snap, active, feeds):
        """Snapshot-recompute for recurrent state: restore the pre-tick
        cache, re-feed each active lane's accepted tokens.  (The refeed
        also rewrites those lanes' pool rows — with identical values, since
        positions and tokens are identical.)"""
        cache = snap
        for s in np.flatnonzero(active):
            cache = self._advance_lane(which, cache, int(s), feeds[int(s)])
        return cache

    # -------------------------------------------------------- stats
    def pool_stats(self) -> dict:
        def pool_bytes(cache, per_shard=False):
            total = 0
            def f(path, a):
                nonlocal total
                if _path_keys(path)[-1] in _POOL_KEYS:
                    n = a.size
                    if per_shard:
                        n = int(np.prod(a.sharding.shard_shape(a.shape)))
                    total += n * a.dtype.itemsize
                return a
            jax.tree_util.tree_map_with_path(f, cache["layers"])
            return total
        stats = {
            "block_size": self.block_size,
            "pool_tokens": self.pool_tokens,
            "num_blocks": self.dspec.num_blocks,
            "cache_pool_bytes": pool_bytes(self.dcache) + pool_bytes(self.tcache),
            "blocks_in_use": self.dalloc.blocks_in_use + self.talloc.blocks_in_use,
            "peak_blocks_in_use": (self.dalloc.peak_in_use
                                   + self.talloc.peak_in_use),
            "shared_blocks_in_use": (
                self.dalloc.sharing_stats()["shared_blocks"]
                + self.talloc.sharing_stats()["shared_blocks"]),
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_tokens_skipped": self.prefill_tokens_skipped,
            "cow_copies": self.cow_copies,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
        }
        if self.prefix_cache is not None:
            stats["prefix_cache"] = self.prefix_cache.stats()
        if self.enc_pool is not None:
            stats["encoder_segments"] = self.enc_pool.stats()
        if self.mesh is not None:
            # per-shard residency: the "model"-sharded pools split their
            # bytes across tensor-parallel shards; block accounting is
            # global (one host-side allocator feeds every shard's tables)
            stats["mesh_devices"] = int(self.mesh.devices.size)
            stats["mesh_axes"] = {k: int(v)
                                  for k, v in self.mesh.shape.items()}
            stats["cache_pool_bytes_per_shard"] = (
                pool_bytes(self.dcache, per_shard=True)
                + pool_bytes(self.tcache, per_shard=True))
        return stats

    def describe(self) -> dict:
        d = super().describe()
        d["pool"] = self.pool_stats()
        return d


# ===================================================================== spec

BACKENDS = ("auto", "single", "batched", "paged", "tree", "tree_slot")


@dataclass(frozen=True)
class EngineSpec:
    """One declarative description of a speculative-serving deployment.

    ``make_engine(draft, target, controller, spec)`` — and
    ``SpecServer(..., spec=...)`` — turn a spec into the right engine, so
    the five engine constructors stop being public API surface.  Fields
    are grouped by what they control; every backend ignores the fields
    that don't apply to it (docs/serving.md has the migration table from
    the old per-engine kwargs).

    * ``backend`` — "single" | "batched" | "paged" | "tree" | "tree_slot",
      or "auto": "paged" when ``pool_tokens`` is set, else "batched" when
      ``batch_size > 1``, else "single".
    * ``batch_size`` — slot count for the slot engines (the old
      ``max_concurrency`` server kwarg).
    * ``fused`` — single-dispatch ragged tick for the batched/paged
      backends (auto-disabled on recurrent stacks).
    * ``tree_paged`` — back the tree backends with B=1 paged pools.
    * precision: ``cache_dtype`` / ``kv_dtype`` ("int8" KV caches) /
      ``quant_draft`` (int8 draft weights).
    * ``prefix_cache`` — paged backend only: refcounted copy-on-write
      prefix sharing with a hashed prefill cache (docs/prefix_sharing.md).
      Streams admitted with an already-cached prompt prefix alias the
      cached blocks instead of re-prefilling them.
    * placement: ``mesh`` (docs/sharding.md).
    * ``drafters`` — a ``core.drafters.DrafterPool``: heterogeneous
      drafter serving on the batched backend (drafter identity as a bandit
      arm, docs/drafters.md).  The pool's default drafter replaces the
      positional ``draft`` bundle; the controller must be a shape
      controller over (drafter x stop-rule) arms.
    """
    backend: str = "auto"
    batch_size: int = 4
    max_len: int = 2048
    temperature: float = 0.0
    greedy: bool = True
    cache_dtype: object = jnp.float32
    kv_dtype: Optional[str] = None
    quant_draft: bool = False
    seed: int = 0
    prefill_chunk: int = 16
    block_size: int = 64
    pool_tokens: Optional[int] = None
    prefix_cache: bool = False
    tree_paged: bool = False
    fused: bool = True
    mesh: object = None
    drafters: object = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")

    def resolve_backend(self) -> str:
        if self.backend != "auto":
            return self.backend
        if self.drafters is not None:
            return "batched"
        if self.pool_tokens is not None:
            return "paged"
        return "batched" if self.batch_size > 1 else "single"


def engine_spec_from_legacy(*, max_len: int = 2048,
                            max_concurrency: int = 8,
                            temperature: float = 0.0, greedy: bool = True,
                            seed: int = 0, paged: bool = False,
                            block_size: int = 64,
                            pool_tokens: Optional[int] = None,
                            tree: bool = False,
                            kv_dtype: Optional[str] = None,
                            quant_draft: bool = False,
                            mesh=None) -> EngineSpec:
    """Map the pre-spec ``SpecServer`` keyword surface onto an
    ``EngineSpec`` (the deprecation shim's translation table)."""
    if tree:
        assert not paged, "tree serving uses per-slot dense caches"
        backend = "tree_slot"
    elif paged:
        backend = "paged"
    else:
        backend = "batched"
    return EngineSpec(backend=backend, batch_size=max_concurrency,
                      max_len=max_len, temperature=temperature,
                      greedy=greedy, seed=seed, block_size=block_size,
                      pool_tokens=pool_tokens, kv_dtype=kv_dtype,
                      quant_draft=quant_draft, mesh=mesh)


def make_engine(draft: ModelBundle, target: ModelBundle,
                controller: Controller, spec: Optional[EngineSpec] = None,
                **fields):
    """THE engine factory: build the backend ``spec`` describes.

    ``make_engine(d, t, c, spec)`` or — convenience — field overrides
    directly: ``make_engine(d, t, c, backend="paged", pool_tokens=4096)``
    (with both, the overrides win via ``dataclasses.replace``)."""
    if spec is None:
        spec = EngineSpec(**fields)
    elif fields:
        spec = replace(spec, **fields)
    backend = spec.resolve_backend()
    if spec.drafters is not None and backend != "batched":
        raise ValueError(
            "drafter pools are a batched-backend feature (got "
            f"backend={backend!r})")
    common = dict(max_len=spec.max_len, temperature=spec.temperature,
                  greedy=spec.greedy, cache_dtype=spec.cache_dtype,
                  kv_dtype=spec.kv_dtype, quant_draft=spec.quant_draft,
                  seed=spec.seed, mesh=spec.mesh)
    if backend == "single":
        return SpecEngine(draft, target, controller, **common)
    if backend == "batched":
        return BatchedSpecEngine(draft, target, controller,
                                 batch_size=spec.batch_size,
                                 prefill_chunk=spec.prefill_chunk,
                                 fused=spec.fused,
                                 drafters=spec.drafters, **common)
    if backend == "paged":
        return PagedSpecEngine(draft, target, controller,
                               batch_size=spec.batch_size,
                               block_size=spec.block_size,
                               pool_tokens=spec.pool_tokens,
                               prefill_chunk=spec.prefill_chunk,
                               fused=spec.fused,
                               prefix_cache=spec.prefix_cache, **common)
    assert isinstance(controller, TapOutTreeSequence), \
        f"{backend} backend needs a TapOutTreeSequence controller"
    if backend == "tree":
        return TreeSpecEngine(draft, target, controller,
                              paged=spec.tree_paged,
                              block_size=spec.block_size, **common)
    return TreeSlotEngine(draft, target, controller,
                          batch_size=spec.batch_size,
                          paged=spec.tree_paged,
                          block_size=spec.block_size, **common)
