"""Jitted public wrappers around the Pallas kernels.

No serving or training program reaches these wrappers: the model code runs
attention through the XLA paths (``repro.models.attention.sdpa``) and the
SSD scan through ``repro.models.ssm.ssd_chunked`` (whose ``use_kernel``
flag nothing sets).  The kernels are validated against the pure-jnp
oracles in ``kernels/ref.py`` in interpret mode only; the TPU compiler
refuses every one of them at real widths (ROADMAP A2).

Dispatch policy: on a TPU backend the kernels are compiled by Mosaic.
Anywhere else a call raises, unless the caller asked for the interpreter
with ``repro.kernels.ops.FORCE_INTERPRET = True`` (the kernel tests and
``benchmarks/bench_kernels.py`` do) -- there is no silent fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import decode_attention as _da
from . import flash_attention as _fa
from . import ssd as _ssd
from . import tree_attention as _ta

FORCE_INTERPRET = False


def _interpret() -> bool:
    if FORCE_INTERPRET:
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"Pallas kernels compile only for TPU (backend is {backend!r}); "
            "set repro.kernels.ops.FORCE_INTERPRET = True to run them in "
            "interpret mode")
    return False


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, qpos, kpos, *, causal: bool = True,
                    window: int = 0, block_q: int = 128, block_k: int = 128):
    return _fa.flash_attention(q, k, v, qpos, kpos, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "block_l"))
def decode_attention(q, k, v, qpos, kpos, *, window: int = 0,
                     block_l: int = 512):
    return _da.decode_attention(q, k, v, qpos, kpos, window=window,
                                block_l=block_l, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window",))
def paged_decode_attention(q, kpool, vpool, tables, lengths, *,
                           window: int = 0):
    return _da.paged_decode_attention(q, kpool, vpool, tables, lengths,
                                      window=window, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "block_l"))
def decode_attention_quant(q, k, kscale, v, vscale, qpos, kpos, *,
                           window: int = 0, block_l: int = 512):
    return _da.decode_attention_quant(q, k, kscale, v, vscale, qpos, kpos,
                                      window=window, block_l=block_l,
                                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window",))
def paged_decode_attention_quant(q, kpool, kscale, vpool, vscale, tables,
                                 lengths, *, window: int = 0):
    return _da.paged_decode_attention_quant(q, kpool, kscale, vpool, vscale,
                                            tables, lengths, window=window,
                                            interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "block_l"))
def ragged_decode_attention(q, k, v, lengths, *, window: int = 0,
                            block_l: int = 512):
    return _da.ragged_decode_attention(q, k, v, lengths, window=window,
                                       block_l=block_l,
                                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "block_l"))
def ragged_decode_attention_quant(q, k, kscale, v, vscale, lengths, *,
                                  window: int = 0, block_l: int = 512):
    return _da.ragged_decode_attention_quant(q, k, kscale, v, vscale,
                                             lengths, window=window,
                                             block_l=block_l,
                                             interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "block_l"))
def ragged_tree_attention(q, k, v, bases, kt, vt, depths, anc, *,
                          window: int = 0, block_l: int = 512):
    return _ta.ragged_tree_attention(q, k, v, bases, kt, vt, depths, anc,
                                     window=window, block_l=block_l,
                                     interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window", "block_l"))
def tree_attention(q, k, v, kpos, base, kt, vt, qpos, anc, *,
                   window: int = 0, block_l: int = 512):
    return _ta.tree_attention(q, k, v, kpos, base, kt, vt, qpos, anc,
                              window=window, block_l=block_l,
                              interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("window",))
def paged_tree_attention(q, kpool, vpool, tables, lengths, kt, vt, depths,
                         anc, *, window: int = 0):
    return _ta.paged_tree_attention(q, kpool, vpool, tables, lengths, kt, vt,
                                    depths, anc, window=window,
                                    interpret=_interpret())


@jax.jit
def ssd_chunk(xc, dtc, dA, dA_cs, Bc, Cc):
    # the cumulative form dA_cs carries everything the kernel needs
    return _ssd.ssd_chunk(xc, dtc, dA, dA_cs, Bc, Cc, interpret=_interpret())
