"""Self-attention: GQA/MQA/MHA, optional sliding window, qk-norm, QKV bias.

Two XLA execution paths, and these are what every serving and training
program runs, on the TPU too.  The Pallas kernels in ``repro.kernels`` are
reached by no program path: they pass their interpret-mode tests, but the
TPU compiler refuses each of them at real widths (ROADMAP A2).

  * ``naive``     — materializes the (Sq, Sk) score matrix; used for small
                    shapes and as the reference.
  * ``flash_xla`` — query-chunked map + kv-chunked scan with online softmax;
                    O(chunk^2) live memory, required for 32k+ dry-runs.

All masking is position-based: key slot ``s`` is visible to query ``i`` iff
``0 <= kpos[s] <= qpos[i]`` and (windowed) ``qpos[i] - kpos[s] < window``.
This single rule covers causal training, ring-buffer decode caches and
rollback-by-pointer (stale slots carry pos -1 or a future position).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .common import apply_rope, dense_init, rms_norm, softcap
from .quant import dequantize_rows, kv_is_quantized, qmatmul, quantize_rows
from .sharding import constrain

NEG_INF = -1e30


# --------------------------------------------------------------- params

def init_attention(key, cfg, *, cross: bool = False, dtype=jnp.float32):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.num_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def qkv_proj(params, cfg, x, positions=None, *, rope: bool = True):
    """Returns q (B,S,H,D), k/v (B,S,G,D); rope applied if positions given."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = qmatmul(x, params["wq"])
    k = qmatmul(x, params["wk"])
    v = qmatmul(x, params["wv"])
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# --------------------------------------------------------------- sdpa

def _mask(qpos, kpos, window: int, causal: bool):
    """(Sq, Sk) boolean visibility mask from absolute positions."""
    m = kpos[None, :] >= 0
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m


def explicit_mask_sdpa(q, k, v, mask, cap=0.0, seq_sharded: bool = False):
    """Score-matrix attention under an EXPLICIT visibility mask.

    q (B,Sq,H,D); k,v (B,Sk,G,D); mask (Sq,Sk) or (B,Sq,Sk) bool.  The
    position-based paths derive their mask from (qpos, kpos); the tree paths
    pass an ancestor mask that positions cannot express (siblings share a
    RoPE position but must not see each other).
    """
    B, Sq, H, D = q.shape
    G = k.shape[2]
    qg = q.reshape(B, Sq, G, H // G, D)
    scores = jnp.einsum("bsgqd,btgd->bgqst", qg, k).astype(jnp.float32)
    if seq_sharded:
        # keep the KV length sharded over "model": XLA then emits the
        # distributed-softmax pattern (partial max/sum + tiny all-reduce)
        # instead of all-gathering the cache (§Perf iteration 2)
        scores = constrain(scores, ("pod", "data"), None, None, None, "model")
    scores = scores / jnp.sqrt(D).astype(jnp.float32)
    scores = softcap(scores, cap)
    if mask.ndim == 2:
        mask = mask[None]
    m = mask[:, None, None]                                  # (B,1,1,Sq,Sk)
    scores = jnp.where(m, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (no valid key yet) -> zeros, not NaN
    p = jnp.where(mask.any(-1)[:, None, None, :, None], p, 0.0)
    out = jnp.einsum("bgqst,btgd->bsgqd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, v.shape[-1])


def _naive_sdpa(q, k, v, qpos, kpos, window, causal, cap=0.0,
                seq_sharded: bool = False):
    return explicit_mask_sdpa(q, k, v, _mask(qpos, kpos, window, causal),
                              cap, seq_sharded=seq_sharded)


def _flash_xla(q, k, v, qpos, kpos, window, causal, cap=0.0,
               q_chunk: int = 512, kv_chunk: int = 1024):
    """Pure-XLA flash attention: scan over KV chunks with online softmax."""
    B, Sq, H, D = q.shape
    G = k.shape[2]
    Dv = v.shape[-1]
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, k.shape[1])
    # pad to multiples
    pq = (-Sq) % qc
    pk = (-k.shape[1]) % kc
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, (0, pq), constant_values=0)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, (0, pk), constant_values=-1)
    Sqp, Skp = q.shape[1], k.shape[1]
    nq, nk = Sqp // qc, Skp // kc
    qs = q.reshape(B, nq, qc, G, H // G, D).transpose(1, 0, 2, 3, 4, 5)
    qps = qpos.reshape(nq, qc)
    ks = k.reshape(B, nk, kc, G, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kc, G, Dv).transpose(1, 0, 2, 3, 4)
    kps = kpos.reshape(nk, kc)
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)

    def q_block(args):
        qb, qp = args  # (B,qc,G,Hg,D), (qc,)

        def kv_step(carry, kv):
            m_i, l_i, acc = carry
            kb, vb, kp = kv
            s = jnp.einsum("bqghd,bkgd->bqghk", qb, kb).astype(jnp.float32) * scale
            s = softcap(s, cap)
            msk = _mask(qp, kp, window, causal)            # (qc, kc)
            s = jnp.where(msk[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m_i, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_i - m_new)
            l_new = l_i * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bqghk,bkgd->bqghd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, qc, G, H // G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, qc, G, H // G), jnp.float32)
        a0 = jnp.zeros((B, qc, G, H // G, Dv), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, kps))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        out = jnp.where((l_f > 0)[..., None], out, 0.0)
        return out.astype(q.dtype)

    out = jax.lax.map(q_block, (qs, qps))                  # (nq,B,qc,G,Hg,D)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sqp, H, Dv)
    return out[:, :Sq]


def sdpa(q, k, v, qpos, kpos, *, window: int = 0, causal: bool = True,
         logits_softcap: float = 0.0, impl: str = "auto",
         seq_sharded: bool = False):
    """Scaled dot-product attention with position-based masking.

    q: (B,Sq,H,D); k,v: (B,Sk,G,D) with H % G == 0.
    qpos: (Sq,) absolute positions of queries; kpos: (Sk,) of keys (-1 =
    invalid slot). seq_sharded: the KV length axis is sharded over "model"
    (set for decode caches whose KV-head count cannot shard) — keeps
    attention local via distributed softmax.
    """
    if impl == "auto":
        flops_proxy = q.shape[1] * k.shape[1]
        impl = "flash_xla" if flops_proxy > 512 * 2048 else "naive"
    if impl == "naive":
        return _naive_sdpa(q, k, v, qpos, kpos, window, causal, logits_softcap,
                           seq_sharded=seq_sharded)
    if impl == "flash_xla":
        return _flash_xla(q, k, v, qpos, kpos, window, causal, logits_softcap)
    raise ValueError(impl)


# --------------------------------------------------------------- blocks

def attn_train(params, cfg, x, positions, *, window: int = 0,
               causal: bool = True, impl: str = "auto"):
    """Full-sequence self-attention (no cache); causal unless encoder."""
    q, k, v = qkv_proj(params, cfg, x, positions)
    q = constrain(q, None, None, "model")
    k = constrain(k, None, None, "model")
    out = sdpa(q, k, v, positions, positions, window=window, causal=causal,
               logits_softcap=cfg.logits_softcap, impl=impl)
    out = out.reshape(x.shape[0], x.shape[1], -1)
    return qmatmul(out, params["wo"])


def _kv_entries(cache_layer, k_new, v_new):
    """The leaf updates a K/V write must apply: {k, v} for float caches,
    {k, v, k_scale, v_scale} (rows quantized here) for int8 caches."""
    if kv_is_quantized(cache_layer):
        kq, ks = quantize_rows(k_new)
        vq, vs = quantize_rows(v_new)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return {"k": k_new, "v": v_new}


def cache_kv(cache_layer, dtype):
    """Read a dense cache layer's K/V as ``dtype`` — dequantizing int8
    payloads against their per-row scales, a plain cast otherwise."""
    if kv_is_quantized(cache_layer):
        return (dequantize_rows(cache_layer["k"], cache_layer["k_scale"], dtype),
                dequantize_rows(cache_layer["v"], cache_layer["v_scale"], dtype))
    return cache_layer["k"].astype(dtype), cache_layer["v"].astype(dtype)


def write_cache(cache_layer, k_new, v_new, pos0, ring: bool):
    """Insert S new K/V rows at absolute position pos0 (traced scalar).
    Int8 caches quantize the rows here and write scale rows alongside."""
    L = cache_layer["k"].shape[1]
    S = k_new.shape[1]
    newpos = pos0 + jnp.arange(S, dtype=jnp.int32)
    entries = _kv_entries(cache_layer, k_new, v_new)
    if not ring:
        out = {key: jax.lax.dynamic_update_slice_in_dim(
                   cache_layer[key], val.astype(cache_layer[key].dtype),
                   pos0, 1)
               for key, val in entries.items()}
        out["pos"] = jax.lax.dynamic_update_slice_in_dim(
            cache_layer["pos"], newpos, pos0, 0)
        return out
    if S >= L:  # only the last L tokens can survive
        entries = {key: val[:, -L:] for key, val in entries.items()}
        newpos = newpos[-L:]
    slots = (newpos % L).astype(jnp.int32)
    out = {key: cache_layer[key].at[:, slots].set(
               val.astype(cache_layer[key].dtype))
           for key, val in entries.items()}
    out["pos"] = cache_layer["pos"].at[slots].set(newpos)
    return out


def attn_cached(params, cfg, x, pos0, cache_layer, *, window: int = 0,
                ring: bool = False, impl: str = "auto"):
    """Prefill/decode step: S new tokens starting at absolute pos0.

    ``ring`` is STATIC (decided by the cache spec at cache-init time): ring
    caches wrap writes modulo the buffer length; full caches use contiguous
    dynamic-update-slice writes.
    """
    B, S, _ = x.shape
    positions = pos0 + jnp.arange(S, dtype=jnp.int32)
    q, k, v = qkv_proj(params, cfg, x, positions)
    cache_layer = write_cache(cache_layer, k, v, pos0, ring=ring)
    # decode caches whose KV-head count can't shard over "model" are
    # sequence-sharded (launch/shardings.cache_spec) -> distributed softmax
    from .sharding import get_mesh
    mesh = get_mesh()
    L = cache_layer["k"].shape[1]
    G = cache_layer["k"].shape[2]
    seq_sharded = bool(
        mesh is not None and "model" in mesh.axis_names and
        G % mesh.shape["model"] != 0 and L % mesh.shape["model"] == 0)
    kk, vv = cache_kv(cache_layer, q.dtype)
    out = sdpa(q, kk, vv, positions,
               cache_layer["pos"], window=window,
               logits_softcap=cfg.logits_softcap, impl=impl,
               seq_sharded=seq_sharded)
    out = out.reshape(B, S, -1)
    return qmatmul(out, params["wo"]), cache_layer


# ------------------------------------------------------------ paged path

def paged_write(pool, new, tables, lengths):
    """Scatter S new per-stream rows into the global block pool.

    pool (N, bs, ...); new (B, S, ...); tables (B, MB); lengths (B,) tokens
    already stored per stream.  Stream b's token at logical position p lands
    in physical row ``tables[b, p // bs] * bs + p % bs``.  Lanes whose table
    row is all-zero (masked/empty slots) write into the trash block 0; the
    allocator never hands block 0 to a stream, so those writes cannot leak
    into a neighbor's pages.

    Sharing invariant (docs/prefix_sharing.md): writes land only at
    positions >= lengths[b], and admission copy-on-writes any refcount>1 /
    immutable block overlapping the stream's write frontier BEFORE the
    first tick — so this scatter only ever touches sole-owner blocks, and
    needs no refcount awareness of its own.  Rollback stays a pure length
    write (``paged_rollback``) for the same reason: shared blocks live
    strictly below the frontier and are never rewritten in place.
    """
    N, bs = pool.shape[0], pool.shape[1]
    B, S = new.shape[:2]
    MB = tables.shape[1]
    offs = lengths[:, None].astype(jnp.int32) + jnp.arange(S, dtype=jnp.int32)
    blk = offs // bs
    phys = jnp.take_along_axis(tables, jnp.clip(blk, 0, MB - 1), axis=1)
    # beyond-table overflow goes to the TRASH block, never a live one —
    # wrapping into tables[b, MB-1] would silently corrupt the stream's
    # own newest rows (engines assert lengths stay within max_len)
    phys = jnp.where(blk < MB, phys, 0)                          # (B, S)
    rows = phys * bs + offs % bs                                 # (B, S)
    flat = pool.reshape((N * bs,) + pool.shape[2:])
    flat = flat.at[rows.reshape(-1)].set(
        new.reshape((B * S,) + new.shape[2:]).astype(pool.dtype))
    return flat.reshape(pool.shape)


def gather_pages(pool, tables):
    """Materialize each stream's logical view (B, MB*bs, ...) of the pool.

    This is the XLA gather path (CPU/correctness); the Pallas kernel
    ``kernels.decode_attention.paged_decode_attention`` streams blocks via
    the table instead of materializing the view.
    """
    N, bs = pool.shape[0], pool.shape[1]
    B, MB = tables.shape
    rows = (tables[:, :, None] * bs +
            jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(B, MB * bs)
    flat = pool.reshape((N * bs,) + pool.shape[2:])
    return flat[rows]                                            # (B, MB*bs, ...)


def paged_write_kv(layer_cache, k_new, v_new, tables, lengths):
    """``paged_write`` for a whole attention layer, quantizing rows first
    when the pools are int8 (scale pools written through the same table)."""
    entries = _kv_entries(layer_cache, k_new, v_new)
    return {key: paged_write(layer_cache[key], val, tables, lengths)
            for key, val in entries.items()}


def gather_kv_pages(layer_cache, tables, dtype):
    """Each stream's logical K/V view (B, MB*bs, G, D) as ``dtype`` —
    gathering and dequantizing the scale pools when the payload is int8."""
    kg = gather_pages(layer_cache["k"], tables)
    vg = gather_pages(layer_cache["v"], tables)
    if kv_is_quantized(layer_cache):
        return (dequantize_rows(kg, gather_pages(layer_cache["k_scale"],
                                                 tables), dtype),
                dequantize_rows(vg, gather_pages(layer_cache["v_scale"],
                                                 tables), dtype))
    return kg.astype(dtype), vg.astype(dtype)


def paged_kpos(lengths, length: int):
    """(B, length) logical key positions, -1 past each stream's length.
    Paged layouts are contiguous per stream, so position == row index."""
    idx = jnp.arange(length, dtype=jnp.int32)[None, :]
    return jnp.where(idx < lengths[:, None], idx, -1)


def sdpa_lanes(q, k, v, qpos, kpos, *, window: int = 0, causal: bool = True,
               logits_softcap: float = 0.0, impl: str = "auto"):
    """``sdpa`` with PER-LANE positions: qpos (B, Sq), kpos (B, Sk).

    Batched serving has every lane at its own sequence position, so the
    shared-position ``sdpa`` cannot serve it; each lane runs the same
    single-stream kernel under vmap (identical shapes -> one program).
    """
    lane = functools.partial(sdpa, window=window, causal=causal,
                             logits_softcap=logits_softcap, impl=impl)
    return jax.vmap(lambda q1, k1, v1, qp, kp:
                    lane(q1[None], k1[None], v1[None], qp, kp)[0])(
                        q, k, v, qpos, kpos)


def attn_paged(params, cfg, x, layer_cache, tables, lengths, *,
               window: int = 0, impl: str = "auto"):
    """Paged prefill/decode step: S new tokens per stream, each stream at
    its own position ``lengths[b]``. Returns (out, new_layer_cache)."""
    B, S, _ = x.shape
    positions = lengths[:, None].astype(jnp.int32) + jnp.arange(S, dtype=jnp.int32)
    q, k, v = qkv_proj(params, cfg, x, positions)
    layer_cache = paged_write_kv(layer_cache, k, v, tables, lengths)
    kg, vg = gather_kv_pages(layer_cache, tables, q.dtype)
    kpos = paged_kpos(lengths + S, kg.shape[1])
    out = sdpa_lanes(q, kg, vg, positions, kpos, window=window,
                     logits_softcap=cfg.logits_softcap, impl=impl)
    out = out.reshape(B, S, -1)
    return qmatmul(out, params["wo"]), layer_cache


# ------------------------------------------------------------ tree path

def init_tree_nodes_attn(cfg, batch: int, dtype):
    """Empty node-KV carry for one attention layer (0 rows; levels append)."""
    hd = cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, 0, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, 0, cfg.num_kv_heads, hd), dtype)}


def attn_tree(params, cfg, x, positions, cache_layer, prev_nodes, node_mask,
              base, *, window: int = 0, impl: str = "auto"):
    """Tree-node attention over ``cache + nodes`` WITHOUT cache writes.

    x (B, Tc, d) current tree nodes; positions (Tc,) their absolute RoPE
    positions (siblings share one); prev_nodes {"k","v"} (B, Tp, G, D) node
    K/V from shallower levels (Tp = 0 on the first feed); node_mask
    (Tc, Tp+Tc) ancestor visibility over [prev, current]; ``base`` the
    cache pointer — only rows with stored position in [0, base) are
    COMMITTED tokens.  The strict ``< base`` rule (vs the chain path's
    ``<= qpos``) is load-bearing: tree passes never overwrite stale rows
    before attending, so rows carrying rolled-back future positions must be
    masked by the pointer, not by the query position.

    Returns (out (B,Tc,d_model), nodes) with nodes = prev + current K/V.
    """
    B, S, _ = x.shape
    q, k, v = qkv_proj(params, cfg, x, positions)
    nodes = {"k": jnp.concatenate([prev_nodes["k"].astype(k.dtype), k], axis=1),
             "v": jnp.concatenate([prev_nodes["v"].astype(v.dtype), v], axis=1)}
    kpos = cache_layer["pos"]
    cmask = (kpos[None, :] >= 0) & (kpos[None, :] < base)        # (1, L)
    if window:
        cmask = cmask & ((positions[:, None] - kpos[None, :]) < window)
    cmask = jnp.broadcast_to(cmask, (S, kpos.shape[0]))          # (Tc, L)
    mask = jnp.concatenate([cmask, node_mask], axis=1)           # (Tc, L+Tn)
    kc, vc = cache_kv(cache_layer, q.dtype)
    # gather [cache rows | node rows] before attending: XLA SPMD miscompiles
    # a concatenate whose operand is sharded on the concat dim when the
    # result length is not divisible by the axis (tree verify appends Tn
    # node rows to the L-row cache), so the concat result must be pinned
    # replicated — the tree pass is one fused forward, the all-gather is
    # its natural KV layout anyway
    kk = constrain(jnp.concatenate([kc, nodes["k"]], axis=1))
    vv = constrain(jnp.concatenate([vc, nodes["v"]], axis=1))
    out = explicit_mask_sdpa(q, kk, vv, mask, cfg.logits_softcap)
    return qmatmul(out.reshape(B, S, -1), params["wo"]), nodes


def attn_tree_paged(params, cfg, x, layer_cache, tables, lengths, depths,
                    prev_nodes, node_mask, *, window: int = 0,
                    impl: str = "auto"):
    """Paged tree-node attention: per-stream positions ``lengths[b] +
    depths``, committed-row validity is the paged ``p < lengths`` rule (no
    stale-row hazard — rows past the length are dead by construction).
    Returns (out, nodes) like ``attn_tree``; the pool is NOT written.
    """
    B, S, _ = x.shape
    positions = lengths[:, None].astype(jnp.int32) + depths[None, :]  # (B,Tc)
    q, k, v = qkv_proj(params, cfg, x, positions)
    nodes = {"k": jnp.concatenate([prev_nodes["k"].astype(k.dtype), k], axis=1),
             "v": jnp.concatenate([prev_nodes["v"].astype(v.dtype), v], axis=1)}
    kg, vg = gather_kv_pages(layer_cache, tables, q.dtype)
    kpos = paged_kpos(lengths, kg.shape[1])                      # (B, L)
    cmask = kpos[:, None, :] >= 0                                # (B, 1, L)
    if window:
        cmask = cmask & ((positions[:, :, None] - kpos[:, None, :]) < window)
    cmask = jnp.broadcast_to(cmask, (B, S, kg.shape[1]))
    nmask = jnp.broadcast_to(node_mask[None], (B,) + node_mask.shape)
    mask = jnp.concatenate([cmask, nmask], axis=2)
    # pin [gathered pages | node rows] replicated (see attn_tree: SPMD
    # concat-on-sharded-dim miscompile)
    kk = constrain(jnp.concatenate([kg, nodes["k"]], axis=1))
    vv = constrain(jnp.concatenate([vg, nodes["v"]], axis=1))
    out = explicit_mask_sdpa(q, kk, vv, mask, cfg.logits_softcap)
    return qmatmul(out.reshape(B, S, -1), params["wo"]), nodes


def commit_tree_rows_attn(cache_layer, nodes, path, n_commit, base):
    """Scatter accepted-path node K/V into a DENSE attention cache.

    path (P,) node row indices (padded past ``n_commit``); rows land at
    slots ``base .. base+P-1``; stored positions are ``base+i`` for
    ``i < n_commit`` and ``-1`` (never visible) for the padding rows, so a
    fixed-width write commits a variable-length path.
    """
    P = path.shape[0]
    rows_k = jnp.take(nodes["k"], path, axis=1)
    rows_v = jnp.take(nodes["v"], path, axis=1)
    entries = _kv_entries(cache_layer, rows_k, rows_v)
    out = {key: jax.lax.dynamic_update_slice_in_dim(
               cache_layer[key], val.astype(cache_layer[key].dtype), base, 1)
           for key, val in entries.items()}
    stored = jnp.where(jnp.arange(P) < n_commit,
                       base + jnp.arange(P, dtype=jnp.int32), -1)
    out["pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache_layer["pos"], stored.astype(jnp.int32), base, 0)
    return out


def commit_tree_rows_paged_attn(layer_cache, nodes, path, tables, lengths):
    """Scatter accepted-path node K/V into the PAGED pool at each stream's
    current length; rows past the engine's subsequent ``lengths + n_commit``
    truncation are dead under the ``p < length`` mask.  Like every paged
    commit, it writes only at positions >= lengths[b] — under prefix
    sharing those blocks are sole-owner by the admission-time COW
    invariant, so the commit stays O(path) and never clones a block."""
    rows_k = jnp.take(nodes["k"], path, axis=1)
    rows_v = jnp.take(nodes["v"], path, axis=1)
    return paged_write_kv(layer_cache, rows_k, rows_v, tables, lengths)


# ------------------------------------------------------- cross-attention

def cross_attn(params, cfg, x, enc, enc_mask=None, impl: str = "auto"):
    """Decoder->encoder attention.

    ``enc`` is either precomputed KV (dict k/v, the decode path) or the raw
    encoder output (B, T, d) from which KV is projected (the train path)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = qmatmul(x, params["wq"]).reshape(B, S, cfg.num_heads, hd)
    if not isinstance(enc, dict):
        enc = encode_cross_kv(params, cfg, enc)
    k, v = enc["k"], enc["v"]
    T = k.shape[1]
    qpos = jnp.zeros((S,), jnp.int32)
    kpos = jnp.zeros((T,), jnp.int32) if enc_mask is None else jnp.where(enc_mask, 0, -1)
    out = sdpa(q, k, v, qpos, kpos, causal=False, impl=impl)
    return qmatmul(out.reshape(B, S, -1), params["wo"])


def encode_cross_kv(params, cfg, enc_out):
    B, T, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ params["wk"]).reshape(B, T, cfg.num_kv_heads, hd)
    v = (enc_out @ params["wv"]).reshape(B, T, cfg.num_kv_heads, hd)
    return {"k": k, "v": v}
