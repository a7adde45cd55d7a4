"""Operations and bytes of the serving programs, from the model's shapes and
each call's own token counts (never from HLO), so that the same work is
counted whatever implements it.

Every count is the LEAST the work needs: matmul FLOPs of the tokens that
are really processed, attention over the keys each of them really sees,
every weight read once per sequential forward pass, every cached key/value
read once per pass and written once.  Work a program wastes (masked lanes,
draft steps past a lane's stop, re-read weights) is left out on purpose, so
a share of the roofline below 100% shows it.

The counts are generic over the model family.  ``tick``, ``prefill`` and
``delivered_flops`` read of a model only what ``ModelDims`` states:
``flops(tokens, keys, head_rows)``, ``weight_bytes(tokens)``,
``head_weight_bytes`` and ``kv_bytes_per_token``.  A family module
(``reference/<name>.py``) builds them with its ``dims(shape)``; ``Dims`` is
the dense decoder's.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, NamedTuple, Protocol, Sequence, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of one device kind; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


class ModelDims(Protocol):
    """What the counts read of one model."""

    @property
    def head_weight_bytes(self) -> int:
        """Bytes of the output head, read once per pass that makes logits."""

    @property
    def kv_bytes_per_token(self) -> int:
        """Bytes of cache one token adds, over every layer."""

    def flops(self, tokens: int, keys: int, head_rows: int) -> float:
        """Matmul FLOPs of a forward over ``tokens`` tokens that together
        attend to ``keys`` keys, with ``head_rows`` rows of logits."""

    def weight_bytes(self, tokens: int) -> int:
        """The least bytes of the layer stack and final norm that one pass
        over ``tokens`` tokens must read (a layer of experts: those the
        tokens reach)."""


class Dims(NamedTuple):
    """``ModelDims`` of a dense GQA decoder with a tied embedding (built by
    ``reference.qwen_dense.dims``)."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False
    qk_norm: bool = False
    weight_bytes_per_param: int = 2
    kv_bytes_per_value: int = 2

    @property
    def layer_matmul_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.d * q + 2 * self.d * kv + q * self.d + 3 * self.d * self.d_ff

    @property
    def layer_params(self) -> int:
        """Matmul weights plus norms, QKV biases and q/k norms."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return (self.layer_matmul_params + 2 * self.d
                + (q + 2 * kv if self.qkv_bias else 0)
                + (2 * self.head_dim if self.qk_norm else 0))

    @property
    def params(self) -> int:
        """Every parameter: the layers, the final norm, the tied embedding."""
        return self.layers * self.layer_params + self.d + self.vocab * self.d

    def weight_bytes(self, tokens: int) -> int:
        """Bytes of the layer stack and the final norm: all of them, however
        few the tokens."""
        return (self.layers * self.layer_params + self.d) * self.weight_bytes_per_param

    @property
    def head_weight_bytes(self) -> int:
        """Bytes of the tied embedding read as the output head."""
        return self.vocab * self.d * self.weight_bytes_per_param

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * self.kv_bytes_per_value

    def flops(self, tokens: int, keys: int, head_rows: int) -> float:
        return (2.0 * self.layers * self.layer_matmul_params * tokens
                + 4.0 * self.layers * self.heads * self.head_dim * keys
                + 2.0 * self.d * self.vocab * head_rows)


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, o):
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def least_s(self, peak: dict) -> Tuple[float, str]:
        """The least time the chip could take, and which bound sets it."""
        tf = self.flops / float(peak["bf16_flops_per_s"])
        tb = self.bytes / float(peak["hbm_bytes_per_s"])
        return (tf, "flops") if tf >= tb else (tb, "bytes")


ZERO = Work(0.0, 0.0)


def _keys(ctx: int, n: int) -> int:
    """Keys seen by ``n`` tokens appended causally after ``ctx`` cached ones."""
    return n * ctx + n * (n + 1) // 2


def tick(target: ModelDims, draft: ModelDims,
         lanes: Iterable[Tuple[int, int]]) -> Work:
    """One serving tick: each lane ``(ctx, drafted)`` had ``ctx`` tokens
    cached, drafted ``drafted`` tokens (one draft step each; the lanes draft
    in lockstep, so draft step ``j`` is one pass over the lanes that draft
    ``j`` tokens or more, and reads the weights those tokens need), and had
    them verified with its last token in ONE target pass."""
    lanes = list(lanes)
    if not lanes:
        return ZERO
    steps = max(d for _, d in lanes)
    n_d = sum(d for _, d in lanes)
    keys_d = sum(_keys(c, d) for c, d in lanes)
    w_d = Work(draft.flops(n_d, keys_d, n_d),
               sum(draft.weight_bytes(sum(d >= j for _, d in lanes))
                   + draft.head_weight_bytes for j in range(1, steps + 1))
               + draft.kv_bytes_per_token * (
                   sum(d * c + d * (d - 1) // 2 for c, d in lanes) + n_d))
    n_t = sum(d + 1 for _, d in lanes)
    keys_t = sum(_keys(c, d + 1) for c, d in lanes)
    w_t = Work(target.flops(n_t, keys_t, n_t),
               target.weight_bytes(n_t) + target.head_weight_bytes
               + target.kv_bytes_per_token * (sum(c for c, _ in lanes) + n_t))
    return w_d + w_t


def prefill(model: ModelDims, tokens: int) -> Work:
    """Admission prefill of ``tokens`` prompt tokens into an empty lane:
    no logits are needed, the layer weights are read once, every new
    key/value is written once."""
    if tokens <= 0:
        return ZERO
    return Work(model.flops(tokens, _keys(0, tokens), 0),
                model.weight_bytes(tokens) + model.kv_bytes_per_token * tokens)


def delivered_flops(target: ModelDims, deliveries: Sequence[Tuple[int, int]]) -> float:
    """Target model FLOPs of delivered tokens; each delivery ``(ctx, k)``
    appended ``k`` tokens after ``ctx`` cached ones."""
    return sum(target.flops(k, _keys(c, k), k) for c, k in deliveries)
