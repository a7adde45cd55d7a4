"""Plain reference of the Qwen2 / Qwen3 dense decoder, and the weights the
benchmark serves.

The forward follows the published description (Hugging Face
``Qwen2ForCausalLM`` / ``Qwen3ForCausalLM``): token embedding; per layer
RMSNorm -> GQA self-attention (QKV bias on Qwen2, per-head RMSNorm of q and
k before RoPE on Qwen3, rotate-half RoPE, causal softmax at 1/sqrt(head_dim))
-> residual -> RMSNorm -> SwiGLU MLP -> residual; final RMSNorm; logits
through the tied embedding.  It is written in straightforward ``jax.numpy``
in float32 under ``jax.default_matmul_precision("highest")``, and imports
nothing of the program.

Weights.  ``make_params`` draws one model from a seed, on the device, in one
jitted call, in the type it is served in (bfloat16), laid out as the
program's parameter tree expects it (``embed``, ``final_norm``, and a
layer ``stack`` with a leading layer axis).  The program stores two things
differently from the published form, and ``published`` maps them back
exactly:

* it multiplies the embedded token by sqrt(hidden_size) and reads logits
  through the unscaled table: the published model with embedding
  ``sqrt(d) * embed`` and final norm weight ``(1 + final_norm) / sqrt(d)``
  computes the same function (tied embeddings, so both ends move);
* it stores every RMSNorm weight as an offset from 1.

Distributions: every projection a normal truncated at 2 standard
deviations with std 1/sqrt(fan_in) (the program's own initializer); the
embedding, the norm offsets and the QKV biases normal with the standard
deviations the configuration's ``weights`` entry gives (nonzero, so that no
part of the layer is an identity the comparison could not see).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

WEIGHT_STDS = ("embed_std", "norm_std", "bias_std")


class Shape(NamedTuple):
    """The sizes of one model, read from a configuration file's ``model``
    (or ``draft``) entry under the published key names."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    qkv_bias: bool
    qk_norm: bool

    @classmethod
    def from_config(cls, m: dict) -> "Shape":
        arch = m.get("architectures", ["Qwen2ForCausalLM"])[0]
        if not m.get("tie_word_embeddings", False):
            raise ValueError("the reference covers tied embeddings only")
        if m.get("hidden_act", "silu") != "silu":
            raise ValueError("the reference covers SwiGLU (silu) MLPs only")
        return cls(int(m["num_hidden_layers"]), int(m["hidden_size"]),
                   int(m["num_attention_heads"]),
                   int(m["num_key_value_heads"]), int(m["head_dim"]),
                   int(m["intermediate_size"]), int(m["vocab_size"]),
                   float(m["rope_theta"]), float(m["rms_norm_eps"]),
                   bool(m.get("attention_bias", arch == "Qwen2ForCausalLM")),
                   arch == "Qwen3ForCausalLM")


# ------------------------------------------------------------- weights

def _layer_shapes(s: Shape) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Program-layout leaves of one layer: shape and initializer."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    mixer = {"wq": ((s.d, q), "dense"), "wk": ((s.d, kv), "dense"),
             "wv": ((s.d, kv), "dense"), "wo": ((q, s.d), "dense")}
    if s.qkv_bias:
        mixer.update(bq=((q,), "bias"), bk=((kv,), "bias"), bv=((kv,), "bias"))
    if s.qk_norm:
        mixer.update(q_norm=((s.head_dim,), "norm"),
                     k_norm=((s.head_dim,), "norm"))
    return {"norm1": ((s.d,), "norm"), "norm2": ((s.d,), "norm"),
            "mixer": mixer,
            "ffn": {"w_in": ((s.d, s.d_ff), "dense"),
                    "w_gate": ((s.d, s.d_ff), "dense"),
                    "w_out": ((s.d_ff, s.d), "dense")}}


def _draw(key, shape, kind, dtype, stds):
    if kind == "dense":
        std = 1.0 / math.sqrt(shape[-2])
        return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                  jnp.float32)).astype(dtype)
    std = stds[WEIGHT_STDS.index(kind + "_std")]
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, s: Shape, dtype, stds):
    leaves, treedef = jax.tree_util.tree_flatten(
        _layer_shapes(s), is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[1], str))
    keys = jax.random.split(key, len(leaves) + 2)
    stack = [_draw(k, (s.layers,) + shape, kind, dtype, stds)
             for k, (shape, kind) in zip(keys[2:], leaves)]
    return {"embed": _draw(keys[0], (s.vocab, s.d), "embed", dtype, stds),
            "final_norm": _draw(keys[1], (s.d,), "norm", dtype, stds),
            "layers": {"prefix": [], "tail": [],
                       "stack": {"0": jax.tree_util.tree_unflatten(treedef,
                                                                   stack)}}}


def make_params(s: Shape, seed_words: Tuple[int, int], weights: dict,
                dtype=jnp.bfloat16):
    """One model from a seed, made on the device in one jitted call, with
    the standard deviations ``weights`` names (``WEIGHT_STDS``).  The
    program scans over its layers when there are two or more, and keeps
    them stacked; a single layer it keeps apart, which is not covered."""
    if s.layers < 2:
        raise ValueError("the program's layer stack needs two layers or more")
    key = jax.random.fold_in(jax.random.PRNGKey(seed_words[0]), seed_words[1])
    stds = tuple(float(weights[k]) for k in WEIGHT_STDS)
    return _make(key, s, jnp.dtype(dtype), stds)


def published(params, s: Shape):
    """The published-form view of ``make_params``' weights (float32 where a
    value is rescaled): embedding, final norm weight, per-layer norm weights.
    Projections and biases are used as stored."""
    root = math.sqrt(s.d)
    return {"embed_scale": root,
            "final_norm": (1.0 + params["final_norm"].astype(jnp.float32)) / root,
            "stack": params["layers"]["stack"]["0"]}


# ------------------------------------------------------------- forward

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE over (T, H, D) at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _quantize(w, precision: str, axis: int):
    """``w`` rounded to a lower precision with one scale per output channel
    (max-abs over ``axis``, the input axis), returned in float32."""
    if precision == "f32":
        return w
    top = {"int8": 127.0, "fp8": 448.0}[precision]
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if precision == "int8":
        q = jnp.clip(jnp.round(w / scale), -127, 127)
    else:
        q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, stack, i, s: Shape, precision: str):
    """One decoder layer over the whole (T, d) sequence, float32."""
    lw = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False
                                               ).astype(jnp.float32), stack)
    m, f = lw["mixer"], lw["ffn"]
    mm = lambda a, w: a @ _quantize(w, precision, 0)
    t = x.shape[0]
    h = _rms(x, 1.0 + lw["norm1"], s.eps)
    q, k, v = mm(h, m["wq"]), mm(h, m["wk"]), mm(h, m["wv"])
    if s.qkv_bias:
        q, k, v = q + m["bq"], k + m["bk"], v + m["bv"]
    q = q.reshape(t, s.heads, s.head_dim)
    k = k.reshape(t, s.kv_heads, s.head_dim)
    v = v.reshape(t, s.kv_heads, s.head_dim)
    if s.qk_norm:
        q = _rms(q, 1.0 + m["q_norm"], s.eps)
        k = _rms(k, 1.0 + m["k_norm"], s.eps)
    q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    rep = s.heads // s.kv_heads              # query head j reads kv head j // rep
    causal = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for g in range(s.kv_heads):
        qg = q[:, g * rep:(g + 1) * rep]                       # (T, rep, D)
        sc = jnp.einsum("qhd,kd->hqk", qg, k[:, g]) / math.sqrt(s.head_dim)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,kd->qhd", p, v[:, g]))
    att = jnp.concatenate(outs, 1).reshape(t, s.heads * s.head_dim)
    x = x + mm(att, m["wo"])
    h = _rms(x, 1.0 + lw["norm2"], s.eps)
    # the program's w_in is the SiLU-gated projection (published gate_proj),
    # w_gate the linear one (up_proj), w_out the down projection
    x = x + mm(jax.nn.silu(mm(h, f["w_in"])) * mm(h, f["w_gate"]), f["w_out"])
    return x


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(tokens, embed, embed_scale: float, precision: str):
    """Embedded tokens; under a lower precision the table is rounded with
    one scale per vocabulary row, as the tied head's is."""
    return _quantize(embed[tokens].astype(jnp.float32) * embed_scale,
                     precision, -1)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head_stats(x_rows, embed, final_norm, tokens, eps: float,
                embed_scale: float, precision: str):
    """Per-row statistics of the logits of hidden rows ``x_rows`` (R, d):
    top logit, its argmax, sum and sum of squares over the vocabulary, and
    the logits of given tokens (``tokens`` (R, k))."""
    w = _quantize(embed.astype(jnp.float32) * embed_scale, precision, -1)
    logits = _rms(x_rows, final_norm, eps) @ w.T               # (R, V)
    return (logits.max(-1), logits.argmax(-1).astype(jnp.int32),
            logits.sum(-1), (logits * logits).sum(-1),
            jnp.take_along_axis(logits, tokens, axis=-1))


class RowStats(NamedTuple):
    top: np.ndarray          # (n,) reference's best logit
    argmax: np.ndarray       # (n,) its token
    at: np.ndarray           # (n, k) logits of the given tokens
    std: float               # std of all n x V logits


def rows(params, s: Shape, seq: np.ndarray, first: int, pad_to: int, *,
         precision: str = "f32", tokens: Optional[np.ndarray] = None,
         block: int = 256) -> RowStats:
    """Run the model over ``seq`` (padded at the end to ``pad_to``; causal,
    so the padding changes no kept row) and return the statistics of the
    rows ``first .. len(seq) - 2``: row ``j`` predicts ``seq[first + j + 1]``.
    ``tokens`` (n, k) gives the token ids per row whose logits are
    returned."""
    pub = published(params, s)
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    n = len(seq) - 1 - first
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(toks), params["embed"], pub["embed_scale"],
                   precision)
        for i in range(s.layers):
            x = _layer(x, pub["stack"], jnp.int32(i), s, precision)
        xr = x[first:first + n]
        if tokens is None:
            tokens = np.zeros((n, 1), np.int32)
        outs = []
        for lo in range(0, n, block):
            hi = min(lo + block, n)
            xb = jnp.zeros((block, s.d), jnp.float32).at[:hi - lo].set(
                xr[lo:hi])
            tb = np.zeros((block, tokens.shape[1]), np.int32)
            tb[:hi - lo] = tokens[lo:hi]
            o = _head_stats(xb, params["embed"], pub["final_norm"],
                            jnp.asarray(tb), s.eps, pub["embed_scale"],
                            precision)
            outs.append([np.asarray(a)[:hi - lo] for a in o])
    top, am, sm, sq, at = (np.concatenate(c) for c in zip(*outs))
    count = n * s.vocab
    mean = sm.astype(np.float64).sum() / count
    var = sq.astype(np.float64).sum() / count - mean * mean
    return RowStats(top, am, at, float(np.sqrt(max(var, 0.0))))
