"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

ops.FORCE_INTERPRET = True


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,H,G,Sq,Sk,D", [
    (1, 2, 1, 64, 64, 64),
    (2, 4, 2, 130, 130, 64),     # padding path
    (1, 8, 1, 96, 96, 128),      # MQA, MXU-aligned head dim
    (2, 4, 4, 33, 70, 32),       # MHA, ragged
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, G, Sq, Sk, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, G, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, G, Sk, D), dtype)
    qpos = jnp.arange(Sq, dtype=jnp.int32) + (Sk - Sq)
    kpos = jnp.arange(Sk, dtype=jnp.int32)
    out = ops.flash_attention(q, k, v, qpos, kpos, block_q=64, block_k=64)
    exp = ref.flash_attention_ref(q, k, v, qpos, kpos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_interpret_mode_only_when_asked(monkeypatch):
    """Off the TPU a kernel call raises unless interpret mode was asked
    for: there is no silent fallback."""
    assert jax.default_backend() != "tpu"
    monkeypatch.setattr(ops, "FORCE_INTERPRET", False)
    with pytest.raises(RuntimeError, match="FORCE_INTERPRET"):
        ops._interpret()
    monkeypatch.setattr(ops, "FORCE_INTERPRET", True)
    assert ops._interpret() is True


def test_flash_attention_window():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32))
    k = jax.random.normal(ks[1], (1, 1, 64, 32))
    v = jax.random.normal(ks[2], (1, 1, 64, 32))
    pos = jnp.arange(64, dtype=jnp.int32)
    out = ops.flash_attention(q, k, v, pos, pos, window=8, block_q=32, block_k=32)
    exp = ref.flash_attention_ref(q, k, v, pos, pos, window=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,H,G,L,D,valid", [
    (1, 2, 1, 256, 64, 256),
    (2, 4, 2, 300, 64, 200),     # ragged + invalid slots
    (1, 8, 8, 128, 128, 100),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, G, L, D, valid, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, G, L, D), dtype)
    v = jax.random.normal(ks[2], (B, G, L, D), dtype)
    kpos = jnp.where(jnp.arange(L) < valid, jnp.arange(L), -1).astype(jnp.int32)
    out = ops.decode_attention(q, k, v, jnp.int32(valid - 1), kpos, block_l=128)
    exp = ref.decode_attention_ref(q, k, v, valid - 1, kpos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_decode_attention_ring_semantics():
    """Stale ring slots (future positions) must be masked out."""
    B, H, G, L, D = 1, 1, 1, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    kpos = jnp.arange(L, dtype=jnp.int32)
    # query at pos 40: slots 41.. are "stale future" entries
    out = ops.decode_attention(q, k, v, jnp.int32(40), kpos, block_l=32)
    exp = ref.decode_attention_ref(q, k[:, :, :41], v[:, :, :41], 40,
                                   kpos[:41])
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


def _random_paged_layout(rng, B, N, bs, MB):
    """Non-overlapping random tables (block 0 = trash) + ragged lengths."""
    perm = rng.permutation(np.arange(1, N))
    tables = np.zeros((B, MB), np.int32)
    lengths = np.zeros((B,), np.int32)
    pi = 0
    for b in range(B):
        # bound by the blocks still unclaimed in the pool, not just MB
        max_tok = min(MB, len(perm) - pi) * bs
        L = int(rng.integers(1, max_tok)) if max_tok > 1 else 1
        nb = -(-L // bs)
        tables[b, :nb] = perm[pi:pi + nb]
        pi += nb
        lengths[b] = L
    return tables, lengths


@pytest.mark.parametrize("B,H,G,N,bs,MB,D,window", [
    (2, 4, 2, 9, 16, 4, 64, 0),
    (3, 2, 1, 17, 8, 6, 32, 0),      # MQA, small blocks
    (2, 8, 8, 9, 16, 4, 128, 0),     # MHA, MXU-aligned head dim
    (2, 4, 2, 9, 16, 4, 64, 12),     # sliding window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(B, H, G, N, bs, MB, D, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kpool = jax.random.normal(ks[1], (N, bs, G, D), dtype)
    vpool = jax.random.normal(ks[2], (N, bs, G, D), dtype)
    tables, lengths = _random_paged_layout(np.random.default_rng(0), B, N, bs, MB)
    out = ops.paged_decode_attention(q, kpool, vpool, jnp.asarray(tables),
                                     jnp.asarray(lengths), window=window)
    exp = ref.paged_decode_attention_ref(q, kpool, vpool, jnp.asarray(tables),
                                         jnp.asarray(lengths), window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_paged_decode_matches_dense_decode():
    """Paged kernel == dense decode kernel on the same logical cache."""
    B, H, G, bs, MB, D = 2, 4, 2, 16, 4, 64
    N = B * MB + 1
    L = MB * bs
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    lengths = np.array([37, 55], np.int32)
    # pack each stream's logical rows into disjoint pool blocks
    tables = np.zeros((B, MB), np.int32)
    kpool = np.zeros((N, bs, G, D), np.float32)
    vpool = np.zeros((N, bs, G, D), np.float32)
    nxt = 1
    for b in range(B):
        for mb in range(MB):
            tables[b, mb] = nxt
            kpool[nxt] = np.asarray(k[b, :, mb * bs:(mb + 1) * bs]).transpose(1, 0, 2)
            vpool[nxt] = np.asarray(v[b, :, mb * bs:(mb + 1) * bs]).transpose(1, 0, 2)
            nxt += 1
    out = ops.paged_decode_attention(q, jnp.asarray(kpool), jnp.asarray(vpool),
                                     jnp.asarray(tables), jnp.asarray(lengths))
    for b in range(B):
        kpos = jnp.where(jnp.arange(L) < lengths[b], jnp.arange(L), -1)
        exp = ops.decode_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                   jnp.int32(lengths[b] - 1),
                                   kpos.astype(jnp.int32), block_l=32)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(exp[0]),
                                   atol=5e-5, rtol=5e-5)


def test_paged_decode_empty_lane_outputs_zero():
    """lengths == 0 (a masked/empty serving lane): every block is fully
    masked, so the kernel must emit zeros — not the mean of the trash rows
    (regression: exp(s - NEG_INF_max) == 1 poisoned the softmax sums)."""
    B, H, G, N, bs, MB, D = 2, 2, 1, 5, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kpool = jax.random.normal(ks[1], (N, bs, G, D))
    vpool = jax.random.normal(ks[2], (N, bs, G, D))
    tables = np.asarray([[0, 0], [1, 2]], np.int32)
    lengths = jnp.asarray([0, 9], jnp.int32)
    out = ops.paged_decode_attention(q, kpool, vpool, jnp.asarray(tables),
                                     lengths)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    exp = ref.paged_decode_attention_ref(q, kpool, vpool, jnp.asarray(tables),
                                         lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


def test_paged_decode_post_rollback_state():
    """Rows past a truncated length are live in HBM but dead to attention:
    truncating lengths must equal never having written the tail."""
    B, H, G, N, bs, MB, D = 1, 2, 1, 7, 8, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kpool = jax.random.normal(ks[1], (N, bs, G, D))
    vpool = jax.random.normal(ks[2], (N, bs, G, D))
    tables = np.asarray([[3, 1, 4, 2]], np.int32)
    full = ops.paged_decode_attention(q, kpool, vpool, jnp.asarray(tables),
                                      jnp.asarray([20], jnp.int32))
    # corrupt the rows past position 20 -> must not change the output
    flat_k, flat_v = np.array(kpool), np.array(vpool)
    for p in range(20, MB * bs):
        blk, off = tables[0, p // bs], p % bs
        flat_k[blk, off] = 1e3
        flat_v[blk, off] = -1e3
    rolled = ops.paged_decode_attention(q, jnp.asarray(flat_k),
                                        jnp.asarray(flat_v),
                                        jnp.asarray(tables),
                                        jnp.asarray([20], jnp.int32))
    np.testing.assert_allclose(np.asarray(full), np.asarray(rolled),
                               atol=5e-5, rtol=5e-5)


# ------------------------------------------------------------- dense ragged

@pytest.mark.parametrize("B,H,G,L,D,window", [
    (2, 4, 2, 256, 64, 0),
    (3, 2, 1, 130, 32, 0),       # padding path, MQA
    (2, 8, 8, 128, 128, 0),      # MHA, MXU-aligned head dim
    (2, 4, 2, 256, 32, 24),      # sliding window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_decode_attention_sweep(B, H, G, L, D, window, dtype):
    """Per-lane lengths via scalar prefetch + pl.when early-exit vs the
    per-lane oracle."""
    ks = jax.random.split(jax.random.PRNGKey(30), 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, G, L, D), dtype)
    v = jax.random.normal(ks[2], (B, G, L, D), dtype)
    rng = np.random.default_rng(30)
    lengths = jnp.asarray(rng.integers(1, L, size=B), jnp.int32)
    out = ops.ragged_decode_attention(q, k, v, lengths, window=window,
                                      block_l=64)
    exp = ref.ragged_decode_attention_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_ragged_decode_matches_per_lane_dense():
    """Ragged kernel == the non-ragged dense kernel called lane by lane."""
    B, H, G, L, D = 3, 4, 2, 192, 64
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    lengths = np.array([17, 192, 65], np.int32)
    out = ops.ragged_decode_attention(q, k, v, jnp.asarray(lengths),
                                      block_l=64)
    for b in range(B):
        kpos = jnp.where(jnp.arange(L) < lengths[b], jnp.arange(L),
                         -1).astype(jnp.int32)
        exp = ops.decode_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                   jnp.int32(lengths[b] - 1), kpos,
                                   block_l=64)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(exp[0]),
                                   atol=5e-5, rtol=5e-5)


def test_ragged_decode_empty_lane_outputs_zero():
    """lengths == 0: every block early-exits, the scratch stays at init,
    and the unguarded finalize must emit zeros."""
    B, H, G, L, D = 2, 2, 1, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(32), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    lengths = jnp.asarray([0, 70], jnp.int32)
    out = ops.ragged_decode_attention(q, k, v, lengths, block_l=32)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    exp = ref.ragged_decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,H,G,L,D,window", [
    (2, 4, 2, 256, 64, 0),
    (3, 2, 1, 130, 32, 0),       # padding path, MQA
    (2, 4, 2, 256, 32, 24),      # sliding window
])
def test_ragged_decode_attention_quant_sweep(B, H, G, L, D, window):
    """Int8 ragged kernel vs the quantized ragged oracle, and within
    quantization error of the fp ragged kernel on the same cache."""
    from repro.models.quant import quantize_rows
    ks = jax.random.split(jax.random.PRNGKey(33), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    kq, kscale = quantize_rows(k)
    vq, vscale = quantize_rows(v)
    rng = np.random.default_rng(33)
    lengths = jnp.asarray(rng.integers(1, L, size=B), jnp.int32)
    out = ops.ragged_decode_attention_quant(q, kq, kscale, vq, vscale,
                                            lengths, window=window,
                                            block_l=64)
    exp = ref.ragged_decode_attention_quant_ref(q, kq, kscale, vq, vscale,
                                                lengths, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)
    fp = ref.ragged_decode_attention_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fp),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("B,H,G,L,D,window", [
    (2, 4, 2, 128, 64, 0),
    (3, 2, 1, 130, 32, 0),       # padding path, MQA
    (2, 4, 2, 128, 32, 24),      # sliding window
])
@pytest.mark.parametrize("treespec", ["chain4", "binary2"])
def test_ragged_tree_attention_sweep(B, H, G, L, D, window, treespec):
    """Per-lane bases via scalar prefetch + pl.when early-exit vs the
    per-lane dense tree oracle."""
    from repro.core import tree as trees
    spec = {"chain4": trees.chain(4), "binary2": trees.binary(2)}[treespec]
    T = spec.n_nodes
    ks = jax.random.split(jax.random.PRNGKey(34), 5)
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    kt = jax.random.normal(ks[3], (B, G, T, D))
    vt = jax.random.normal(ks[4], (B, G, T, D))
    rng = np.random.default_rng(34)
    bases = jnp.asarray(rng.integers(1, L, size=B), jnp.int32)
    depths = jnp.asarray(spec.depths, jnp.int32)
    anc = jnp.asarray(spec.ancestor_mask, jnp.int32)
    out = ops.ragged_tree_attention(q, k, v, bases, kt, vt, depths, anc,
                                    window=window, block_l=64)
    exp = ref.ragged_tree_attention_ref(q, k, v, bases, kt, vt, depths, anc,
                                        window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


def test_ragged_tree_empty_lane_attends_tree_only():
    """bases == 0: every cache block early-exits; nodes still attend their
    ancestors, so the output equals tree-only attention (not zeros)."""
    from repro.core import tree as trees
    spec = trees.chain(3)
    B, H, G, L, D = 1, 2, 1, 64, 32
    T = spec.n_nodes
    ks = jax.random.split(jax.random.PRNGKey(35), 5)
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    kt = jax.random.normal(ks[3], (B, G, T, D))
    vt = jax.random.normal(ks[4], (B, G, T, D))
    depths = jnp.asarray(spec.depths, jnp.int32)
    anc = jnp.asarray(spec.ancestor_mask, jnp.int32)
    out = ops.ragged_tree_attention(q, k, v, jnp.zeros((B,), jnp.int32),
                                    kt, vt, depths, anc, block_l=32)
    exp = ref.flash_attention_ref(q, kt, vt, depths,
                                  jnp.arange(T, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


# --------------------------------------------------------------- quantized

@pytest.mark.parametrize("B,H,G,L,D,valid,window", [
    (1, 2, 1, 256, 64, 256, 0),
    (2, 4, 2, 300, 64, 200, 0),      # ragged + invalid slots
    (1, 8, 8, 128, 128, 100, 0),     # MHA, MXU-aligned head dim
    (2, 4, 2, 256, 32, 180, 24),     # sliding window
])
def test_decode_attention_quant_sweep(B, H, G, L, D, valid, window):
    """Int8 dequant-in-register decode kernel vs the quantized oracle, and
    within quantization error of the fp kernel on the same cache."""
    from repro.models.quant import quantize_rows
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    kq, kscale = quantize_rows(k)
    vq, vscale = quantize_rows(v)
    kpos = jnp.where(jnp.arange(L) < valid, jnp.arange(L), -1).astype(jnp.int32)
    out = ops.decode_attention_quant(q, kq, kscale, vq, vscale,
                                     jnp.int32(valid - 1), kpos,
                                     window=window, block_l=128)
    exp = ref.decode_attention_quant_ref(q, kq, kscale, vq, vscale,
                                         valid - 1, kpos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)
    fp = ref.decode_attention_ref(q, k, v, valid - 1, kpos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fp),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("B,H,G,N,bs,MB,D,window", [
    (2, 4, 2, 9, 16, 4, 64, 0),
    (3, 2, 1, 17, 8, 6, 32, 0),      # MQA, small blocks
    (2, 8, 8, 9, 16, 4, 128, 0),     # MHA, MXU-aligned head dim
    (2, 4, 2, 9, 16, 4, 64, 12),     # sliding window
])
def test_paged_decode_attention_quant_sweep(B, H, G, N, bs, MB, D, window):
    """Int8 paged kernel (scalar-prefetch payload + scale pools) vs the
    quantized paged oracle."""
    from repro.models.quant import quantize_rows
    ks = jax.random.split(jax.random.PRNGKey(22), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kpool = jax.random.normal(ks[1], (N, bs, G, D))
    vpool = jax.random.normal(ks[2], (N, bs, G, D))
    kq, kscale = quantize_rows(kpool)
    vq, vscale = quantize_rows(vpool)
    tables, lengths = _random_paged_layout(np.random.default_rng(4), B, N, bs, MB)
    out = ops.paged_decode_attention_quant(
        q, kq, kscale, vq, vscale, jnp.asarray(tables), jnp.asarray(lengths),
        window=window)
    exp = ref.paged_decode_attention_quant_ref(
        q, kq, kscale, vq, vscale, jnp.asarray(tables), jnp.asarray(lengths),
        window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


def test_paged_decode_quant_empty_lane_outputs_zero():
    """lengths == 0 under int8 pools: fully-masked lanes still emit zeros
    (the re-mask guard must survive the scale multiplies)."""
    from repro.models.quant import quantize_rows
    B, H, G, N, bs, MB, D = 2, 2, 1, 5, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kq, kscale = quantize_rows(jax.random.normal(ks[1], (N, bs, G, D)))
    vq, vscale = quantize_rows(jax.random.normal(ks[2], (N, bs, G, D)))
    tables = jnp.asarray([[0, 0], [1, 2]], jnp.int32)
    lengths = jnp.asarray([0, 9], jnp.int32)
    out = ops.paged_decode_attention_quant(q, kq, kscale, vq, vscale,
                                           tables, lengths)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)


# --------------------------------------------------------------- tree

def _tree_fixtures(key, B, H, G, L, D, spec):
    ks = jax.random.split(key, 5)
    T = spec.n_nodes
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, G, L, D))
    v = jax.random.normal(ks[2], (B, G, L, D))
    kt = jax.random.normal(ks[3], (B, G, T, D))
    vt = jax.random.normal(ks[4], (B, G, T, D))
    return q, k, v, kt, vt


@pytest.mark.parametrize("B,H,G,L,D,base,window", [
    (1, 2, 1, 128, 64, 100, 0),
    (2, 4, 2, 130, 64, 90, 0),       # padding path, GQA
    (1, 8, 1, 96, 128, 96, 0),       # MQA, MXU-aligned head dim
    (2, 4, 2, 128, 32, 100, 24),     # sliding window
])
@pytest.mark.parametrize("treespec", ["chain4", "binary2", "b3x2x1"])
def test_tree_attention_sweep(B, H, G, L, D, base, window, treespec):
    from repro.core import tree as trees
    spec = {"chain4": trees.chain(4), "binary2": trees.binary(2),
            "b3x2x1": trees.from_branching((3, 2, 1))}[treespec]
    q, k, v, kt, vt = _tree_fixtures(jax.random.PRNGKey(11), B, H, G, L, D,
                                     spec)
    # rows base..base+9 carry stale future positions: the < base rule must
    # mask them even though kpos <= qpos would admit them
    kpos = jnp.where(jnp.arange(L) < base + 10, jnp.arange(L), -1).astype(jnp.int32)
    qpos = jnp.asarray(base + spec.depths, jnp.int32)
    anc = jnp.asarray(spec.ancestor_mask, jnp.int32)
    out = ops.tree_attention(q, k, v, kpos, jnp.int32(base), kt, vt, qpos,
                             anc, window=window, block_l=64)
    exp = ref.tree_attention_ref(q, k, v, kpos, base, kt, vt, qpos, anc,
                                 window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


def test_tree_attention_chain_matches_flash():
    """A chain-topology tree block == ordinary causal attention over the
    same [cache + suffix] sequence."""
    from repro.core import tree as trees
    B, H, G, L, D = 1, 2, 1, 64, 32
    spec = trees.chain(4)
    q, k, v, kt, vt = _tree_fixtures(jax.random.PRNGKey(12), B, H, G, L, D,
                                     spec)
    base = 40
    kpos = jnp.where(jnp.arange(L) < base, jnp.arange(L), -1).astype(jnp.int32)
    qpos = jnp.asarray(base + spec.depths, jnp.int32)
    anc = jnp.asarray(spec.ancestor_mask, jnp.int32)
    out = ops.tree_attention(q, k, v, kpos, jnp.int32(base), kt, vt, qpos,
                             anc, block_l=32)
    kcat = jnp.concatenate([k[:, :, :base], kt], axis=2)
    vcat = jnp.concatenate([v[:, :, :base], vt], axis=2)
    exp = ref.flash_attention_ref(q, kcat, vcat, qpos,
                                  jnp.arange(base + 4, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,H,G,N,bs,MB,D,window", [
    (2, 4, 2, 9, 16, 4, 64, 0),
    (3, 2, 1, 17, 8, 6, 32, 0),      # MQA, small blocks
    (2, 8, 8, 9, 16, 4, 128, 0),     # MHA, MXU-aligned head dim
    (2, 4, 2, 9, 16, 4, 64, 12),     # sliding window
])
@pytest.mark.parametrize("treespec", ["binary2", "wide3x2"])
def test_paged_tree_attention_sweep(B, H, G, N, bs, MB, D, window, treespec):
    from repro.core import tree as trees
    spec = {"binary2": trees.binary(2), "wide3x2": trees.wide(3, 2)}[treespec]
    T = spec.n_nodes
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    q = jax.random.normal(ks[0], (B, H, T, D))
    kpool = jax.random.normal(ks[1], (N, bs, G, D))
    vpool = jax.random.normal(ks[2], (N, bs, G, D))
    kt = jax.random.normal(ks[3], (B, G, T, D))
    vt = jax.random.normal(ks[4], (B, G, T, D))
    tables, lengths = _random_paged_layout(np.random.default_rng(3), B, N, bs, MB)
    depths = jnp.asarray(spec.depths, jnp.int32)
    anc = jnp.asarray(spec.ancestor_mask, jnp.int32)
    out = ops.paged_tree_attention(q, kpool, vpool, jnp.asarray(tables),
                                   jnp.asarray(lengths), kt, vt, depths, anc,
                                   window=window)
    exp = ref.paged_tree_attention_ref(q, kpool, vpool, jnp.asarray(tables),
                                       jnp.asarray(lengths), kt, vt, depths,
                                       anc, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


def test_paged_tree_empty_lane_attends_tree_only():
    """lengths == 0: every cache block is masked; nodes still attend their
    ancestors, so the output equals tree-only attention (not zeros)."""
    from repro.core import tree as trees
    spec = trees.chain(3)
    B, H, G, N, bs, MB, D = 1, 2, 1, 5, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(14), 5)
    T = spec.n_nodes
    q = jax.random.normal(ks[0], (B, H, T, D))
    kpool = jax.random.normal(ks[1], (N, bs, G, D))
    vpool = jax.random.normal(ks[2], (N, bs, G, D))
    kt = jax.random.normal(ks[3], (B, G, T, D))
    vt = jax.random.normal(ks[4], (B, G, T, D))
    tables = jnp.zeros((1, MB), jnp.int32)
    lengths = jnp.zeros((1,), jnp.int32)
    depths = jnp.asarray(spec.depths, jnp.int32)
    anc = jnp.asarray(spec.ancestor_mask, jnp.int32)
    out = ops.paged_tree_attention(q, kpool, vpool, tables, lengths, kt, vt,
                                   depths, anc)
    exp = ref.flash_attention_ref(q, kt, vt, depths,
                                  jnp.arange(T, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("B,NC,Q,H,P,G,N", [
    (1, 2, 16, 2, 32, 1, 16),
    (2, 3, 16, 4, 32, 2, 16),    # grouped B/C
    (1, 1, 64, 8, 64, 1, 128),   # mamba2-like dims
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_sweep(B, NC, Q, H, P, G, N, dtype):
    kk = jax.random.split(jax.random.PRNGKey(4), 5)
    xc = jax.random.normal(kk[0], (B, NC, Q, H, P), dtype)
    dtc = jax.nn.softplus(jax.random.normal(kk[1], (B, NC, Q, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(kk[2], (H,)))
    dA = dtc.astype(jnp.float32) * A
    dA_cs = jnp.cumsum(dA, axis=2)
    Bc = jax.random.normal(kk[3], (B, NC, Q, G, N), dtype)
    Cc = jax.random.normal(kk[4], (B, NC, Q, G, N), dtype)
    yk, stk = ops.ssd_chunk(xc, dtc, dA, dA_cs, Bc, Cc)
    yr, sr = ref.ssd_chunk_ref(xc.astype(jnp.float32), dtc.astype(jnp.float32),
                               dA, dA_cs, Bc.astype(jnp.float32),
                               Cc.astype(jnp.float32))
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), **tol)
    np.testing.assert_allclose(np.asarray(stk), np.asarray(sr), **tol)


def test_ssd_kernel_inside_model_path():
    """ssd_chunked(use_kernel=True) == XLA path on full scan."""
    from repro.models.ssm import ssd_chunked
    kk = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(kk[0], (2, 48, 4, 32))
    dt = jax.nn.softplus(jax.random.normal(kk[1], (2, 48, 4)))
    A = -jnp.exp(jax.random.normal(kk[2], (4,)))
    Bm = jax.random.normal(kk[3], (2, 48, 2, 16))
    Cm = jax.random.normal(kk[4], (2, 48, 2, 16))
    y1, s1 = ssd_chunked(x, dt, A, Bm, Cm, 16, use_kernel=False)
    y2, s2 = ssd_chunked(x, dt, A, Bm, Cm, 16, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4,
                               rtol=1e-4)
