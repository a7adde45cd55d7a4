"""The model family interface: the harness takes the program's config, the
least-work dims and the KV layout from the family module a configuration
names, so that a new family is a new file and no edit of the harness."""
import dataclasses
import sys
import types
from typing import NamedTuple

import pytest

from .conftest import ROOT, make_root, tiny_config
from onchip import counts, harness

# ------------------------------------------------- the dense family, pinned

# what the harness gave for qwen2.5-3b.rag_open before the counts and the
# program's config came from the family module
QWEN_LAYOUT = harness.Layout(lanes=16, slot_tokens=4416, pool_tokens=65088,
                             kv_bytes_per_token=46080,
                             mean_reserve_tokens=1375.25)
QWEN_PARAMS = (3_085_938_688, 331_383_296)
QWEN_TICK = counts.Work(flops=115085979648.0, bytes=11783769088)
QWEN_PREFILL = counts.Work(flops=5831160496128.0, bytes=5587259392)
QWEN_DELIVERED = 25587122176.0


def _qwen_program_config(name, layers, d, heads, d_ff):
    """The dense decoder's ``ModelConfig`` as the harness built it."""
    from repro.models import ModelConfig
    return ModelConfig(
        name=name, arch_type="dense", num_layers=layers, d_model=d,
        num_heads=heads, num_kv_heads=2, head_dim=128, d_ff=d_ff,
        vocab_size=151936, activation="swiglu", qk_norm=False, qkv_bias=True,
        rope_theta=1000000.0, rms_eps=1e-6, tie_embeddings=True,
        block_pattern=("attn",))


def test_qwen_cell_keeps_its_layout_counts_and_program():
    cell = harness.load_cell("qwen2.5-3b.rag_open", ROOT)
    fam = cell.family
    assert fam.__name__ == "onchip.reference.qwen_dense"
    shapes = [fam.Shape.from_config(cell.config[k]) for k in ("model", "draft")]
    t, d = (fam.dims(s) for s in shapes)
    assert harness.Layout.of(cell.config, cell.traffic, t, d) == QWEN_LAYOUT
    assert (t.params, d.params) == QWEN_PARAMS
    assert counts.tick(t, d, [(1000, 4), (2000, 8), (300, 1)]) == QWEN_TICK
    assert counts.prefill(t, 1023) == QWEN_PREFILL
    assert counts.delivered_flops(t, [(1000, 3), (50, 1)]) == QWEN_DELIVERED
    want = [_qwen_program_config("qwen2.5-3b", 36, 2048, 16, 11008),
            _qwen_program_config("qwen2.5-3b-draft", 9, 1024, 8, 5504)]
    for shape, name, w in zip(shapes, ("qwen2.5-3b", "qwen2.5-3b-draft"), want):
        got = fam.program_config(shape, name)
        for f in dataclasses.fields(w):
            assert getattr(got, f.name) == getattr(w, f.name), f.name
        assert got == w


# ------------------------------------------------- a family in new files

class _StubShape(NamedTuple):
    layers: int
    vocab: int
    kv: int

    @classmethod
    def from_config(cls, m):
        return cls(int(m["layers"]), int(m["vocab"]), int(m["kv"]))


class _StubDims(NamedTuple):
    """Experts: a pass reads more weights the more tokens it routes."""
    kv_bytes_per_token: int
    head_weight_bytes: int = 7

    def flops(self, tokens, keys, head_rows):
        return float(tokens + keys + head_rows)

    def weight_bytes(self, tokens):
        return 1000 + 10 * min(tokens, 8)


def _stub_family(**drop):
    mod = types.ModuleType("onchip.reference.stub_experts")
    mod.Shape = _StubShape
    mod.make_params = mod.rows = mod.program_config = lambda *a, **k: None
    mod.dims = lambda s: _StubDims(s.kv)
    for name in drop:
        delattr(mod, name)
    return mod


def _stub_config():
    cfg = tiny_config()
    cfg.update(reference="stub_experts",
               model={"layers": 3, "vocab": 512, "kv": 300},
               draft={"layers": 1, "vocab": 512, "kv": 60})
    return cfg


@pytest.fixture
def stub_root(tmp_path, monkeypatch):
    monkeypatch.setitem(sys.modules, "onchip.reference.stub_experts",
                        _stub_family())
    return make_root(tmp_path, _stub_config())


def test_new_family_is_resolved_by_name(stub_root):
    cell = harness.load_cell("tiny.open", stub_root)
    fam = cell.family
    assert fam.__name__ == "onchip.reference.stub_experts"
    t, d = (fam.dims(fam.Shape.from_config(cell.config[k]))
            for k in ("model", "draft"))
    lay = harness.Layout.of(cell.config, cell.traffic, t, d)
    block = cell.config["serving"]["block_size"]
    assert lay.kv_bytes_per_token == 360
    assert lay.pool_tokens == int(2.0e6 // (360 * block)) * block

    # lanes (ctx, drafted): the draft's steps pass over 3, 2 and 2 lanes
    lanes = [(10, 3), (20, 1), (5, 0), (7, 3)]
    w = counts.tick(t, d, lanes)
    draft_weights = sum(d.weight_bytes(n) + 7 for n in (3, 2, 2))
    assert draft_weights == 1030 + 1020 + 1020 + 21
    draft_kv = 60 * ((3 * 10 + 3) + 20 + (3 * 7 + 3) + 3 + 1 + 3)
    # the target verifies 4 + 2 + 1 + 4 tokens in one pass
    target = t.weight_bytes(11) + 7 + 300 * (10 + 20 + 5 + 7 + 11)
    assert w.bytes == draft_weights + draft_kv + target
    assert counts.prefill(t, 40) == counts.Work(40 + 820, 1080 + 300 * 40)

    # the readers read the family's dims through the counts alone
    rec = harness.Record()
    rec.admissions.append((0.5, 41))
    rec.deliveries.append((0, 1.0, 2, 50))
    view = harness.RunView(2.0, rec, {}, 0.0, 2.0, t, d,
                           {"bf16_flops_per_s": 1000.0})
    mfu = cell.reader("mfu.rag")(view)
    assert mfu == pytest.approx(100.0 * (860 + 2 + 103 + 2) / 2.0 / 1000.0)


@pytest.mark.parametrize("missing", harness.FAMILY_API)
def test_incomplete_family_is_refused(tmp_path, monkeypatch, missing):
    monkeypatch.setitem(sys.modules, "onchip.reference.stub_experts",
                        _stub_family(**{missing: True}))
    root = make_root(tmp_path, _stub_config())
    with pytest.raises(harness.BenchError, match=f"lacks {missing}$"):
        harness.load_cell("tiny.open", root)


def test_unknown_family_is_refused(tmp_path):
    root = make_root(tmp_path, dict(tiny_config(), reference="no_such_family"))
    with pytest.raises(harness.BenchError, match="no model family module"):
        harness.load_cell("tiny.open", root)
