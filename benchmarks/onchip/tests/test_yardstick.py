"""The benchmark's arithmetic on hand-made inputs: end-to-end metrics,
operation and byte counts, traffic generation, trace reduction."""
import json

import numpy as np
import pytest

from .conftest import BENCH, ROOT, TINY_CLOSED
from onchip import counts, harness, trace
from onchip.gen.traffic import make_traffic, pool_lengths


def _log(rid, due, first):
    return harness.ReqLog(rid, rid, 10, 10, due, due, first=first)


# ------------------------------------------------------------ end to end

def _steady(stall_at=None, stall_s=0.0):
    """Two requests delivering every 0.1 s from t=0.1 to t=10; an optional
    stall delays every delivery after ``stall_at`` by ``stall_s``."""
    rec = harness.Record()
    for i in range(1, 101):
        t = 0.1 * i + (stall_s if stall_at is not None and 0.1 * i > stall_at else 0.0)
        for rid in (0, 1):
            rec.deliveries.append((rid, t, 2, 100 + 2 * i))
    return rec


def test_itl_samples_and_percentile():
    rec = _steady()
    s = harness.itl_samples(rec, 0.0, 20.0)
    assert len(s) == 2 * 99 and np.allclose(s, 0.1)
    assert harness.end_to_end("itl_p95_ms", rec, {}, 0.0, 20.0, 0.0) == pytest.approx(100.0)
    # only deliveries inside the window count, but the gap may start before it
    assert len(harness.itl_samples(rec, 5.0, 20.0)) == 2 * 50


def test_stall_moves_itl_p99_not_p95():
    """Stalls on 2% of the deliveries, as admission prefills give the
    decoding lanes: the 99th percentile sees them, the 95th does not."""
    base = harness.end_to_end("itl_p99_ms", _steady(), {}, 0.0, 20.0, 0.0)
    rec = harness.Record()
    for i in range(1, 101):
        t = 0.1 * i + 0.5 * max(0, min(i - 40, 2))
        for rid in (0, 1):
            rec.deliveries.append((rid, t, 2, 0))
    assert base == pytest.approx(100.0)
    assert harness.end_to_end("itl_p95_ms", rec, {}, 0.0, 20.0, 0.0) == pytest.approx(100.0)
    assert harness.end_to_end("itl_p99_ms", rec, {}, 0.0, 20.0, 0.0) == pytest.approx(600.0)


def test_stall_moves_itl_mean_by_its_time():
    """The mean inter-token time takes every stall at its length: two
    stalls of 0.5 s per request over 99 gaps each add 1 s / 99."""
    base = harness.end_to_end("itl_mean_ms", _steady(), {}, 0.0, 20.0, 0.0)
    rec = harness.Record()
    for i in range(1, 101):
        t = 0.1 * i + 0.5 * max(0, min(i - 40, 2))
        for rid in (0, 1):
            rec.deliveries.append((rid, t, 2, 0))
    assert base == pytest.approx(100.0)
    assert harness.end_to_end("itl_mean_ms", rec, {}, 0.0, 20.0, 0.0) == pytest.approx(
        100.0 + 1000.0 / 99)
    assert harness.end_to_end("itl_mean_ms", harness.Record(), {}, 0.0, 20.0, 0.0) is None


def test_itl_p99_reader_reads_the_window():
    cell = harness.load_cell("qwen2.5-3b.rag_open", ROOT)
    rec = _steady(stall_at=5.0, stall_s=0.5)
    view = harness.RunView(20.0, rec, {}, 0.0, 20.0, None, None, {})
    assert cell.reader("itl_p99_ms.admission")(view) == pytest.approx(
        harness.end_to_end("itl_p99_ms", rec, {}, 0.0, 20.0, 0.0))
    empty = harness.RunView(20.0, harness.Record(), {}, 0.0, 20.0, None, None, {})
    assert cell.reader("itl_p99_ms.admission")(empty) is None


def test_output_rate_counts_window_tokens_only():
    rec = _steady()
    # deliveries at 0.1 .. 10.0, two requests, two tokens each
    assert harness.end_to_end("output_tok_s", rec, {}, 5.0, 10.0, 0.0) == pytest.approx(
        2 * 2 * 50 / 5.0)


def test_ttft_from_due_time():
    logs = {0: _log(0, 1.0, 2.5), 1: _log(1, 2.0, 2.6), 2: _log(2, 0.0, 0.5),
            3: _log(3, 9.0, None)}
    # request 2's first token is before the window, 3 has none
    assert sorted(harness.ttft_samples(logs, 1.0, 10.0)) == pytest.approx([0.6, 1.5])
    assert harness.end_to_end("ttft_p50_ms", harness.Record(), logs, 1.0, 10.0,
                              0.0) == pytest.approx(1050.0)
    assert harness.end_to_end("setup_s", harness.Record(), logs, 1.0, 10.0,
                              12.5) == 12.5


# ------------------------------------------------------------ counts

def _dims(name, which="model"):
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    fam = harness.family_module(c)
    return fam.dims(fam.Shape.from_config(c[which]))


def test_counts_qwen25_3b_by_hand():
    t, d = _dims("qwen2.5-3b"), _dims("qwen2.5-3b", "draft")
    # per layer: q 2048x2048, k and v 2048x256, o 2048x2048, 3 x 2048x11008
    layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert t.layer_matmul_params == layer == 77_070_336
    # + norms 2x2048, biases 2048 + 2 x 256; final norm; tied embedding
    assert t.params == 36 * (layer + 4096 + 2560) + 2048 + 151936 * 2048 \
        == 3_085_938_688
    assert d.params == 331_383_296
    assert t.kv_bytes_per_token == 36 * 2 * 2 * 128 * 2 == 36_864
    # one lane: 100 tokens cached, 2 drafted, then 3 verified
    w = counts.tick(t, d, [(100, 2)])
    flops_t = 2 * 36 * layer * 3 + 4 * 36 * 16 * 128 * (101 + 102 + 103) \
        + 2 * 2048 * 151936 * 3
    flops_d = 2 * 9 * d.layer_matmul_params * 2 + 4 * 9 * 8 * 128 * (101 + 102) \
        + 2 * 1024 * 151936 * 2
    assert w.flops == pytest.approx(flops_t + flops_d)
    bytes_t = 2 * (t.params - 0) + 36_864 * (100 + 3)
    bytes_d = 2 * 2 * d.params + 9216 * (100 + 101 + 2)
    assert w.bytes == pytest.approx(bytes_t + bytes_d)
    # prefill of 1023 tokens: no logits, the stack read once
    p = counts.prefill(t, 1023)
    assert p.flops == pytest.approx(2 * 36 * layer * 1023
                                    + 4 * 36 * 16 * 128 * 1023 * 1024 / 2)
    assert p.bytes == pytest.approx(2 * (t.params - 151936 * 2048) + 36_864 * 1023)


def test_least_time_names_its_bound():
    peak = counts.peaks("TPU v5 lite")
    assert counts.Work(197e12, 1.0).least_s(peak) == (pytest.approx(1.0), "flops")
    assert counts.Work(1.0, 819e9).least_s(peak) == (pytest.approx(1.0), "bytes")
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


# ------------------------------------------------------------ traffic

TRAFFIC = {p.stem: json.loads(p.read_text())
           for p in sorted((BENCH / "traffic").glob("*.json"))}
TRAFFIC["tiny_closed"] = TINY_CLOSED


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_traffic_is_deterministic_per_seed(name):
    spec = TRAFFIC[name]
    a = make_traffic(name, spec, 2 ** 31 + 5, 151936)
    b = make_traffic(name, spec, 2 ** 31 + 5, 151936)
    c = make_traffic(name, spec, 7, 151936)
    assert all((x == y).all() for x, y in zip(a.prompts, b.prompts))
    assert a.max_new == b.max_new
    # another seed: other ids, the same sizes and arrivals in the same order
    assert not (a.prompts[0][:8] == c.prompts[0][:8]).all()
    assert [len(p) for p in a.prompts] == [len(p) for p in c.prompts]
    assert a.max_new == c.max_new
    plens, olens = pool_lengths(spec)
    assert list(plens) == [len(p) for p in a.prompts] and list(olens) == a.max_new
    assert plens.min() >= spec["prompt"]["lo"] and plens.max() <= spec["prompt"]["hi"]
    assert olens.min() >= spec["output"]["lo"] and olens.max() <= spec["output"]["hi"]
    if spec["loop"] == "open":
        assert np.allclose(a.gaps_s, c.gaps_s)
        assert np.mean(a.gaps_s) == pytest.approx(
            1 / spec["arrivals"]["rate"], rel=0.15)


# ------------------------------------------------------------ trace

def _ev(plane, line, name, start_us, dur_us):
    return trace.Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


def test_trace_reduction_by_hand():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        _ev(host, "python", trace.WINDOW_SPAN, 0, 1000),
        _ev(host, "python", "bench.step", 0, 600),
        _ev(host, "python", "engine.session_step_flush", 0, 300),
        _ev(host, "python", "scheduler.schedule", 300, 200),
        _ev(dev, "XLA Modules", "jit_fused_session_tick(12)", 500, 300),
        _ev(dev, "XLA Ops", "fusion.1", 500, 100),
        _ev(dev, "XLA Ops", "fusion.2", 600, 200),
        _ev(dev, "XLA Ops", "fusion.1", 810, 10),      # 10 us gap: between ops
        _ev(dev, "XLA Ops", "fusion.9", 1200, 50),     # after the window
    ]
    r = trace.reduce_events(events, harness.SPANS)
    assert r.window_s == pytest.approx(1e-3)
    assert r.busy_s == pytest.approx(310e-6)
    assert r.idle_share == pytest.approx(0.69)
    assert r.module_time("fused_session_tick") == (pytest.approx(300e-6), 1)
    idle = dict(r.idle_by_host)
    assert idle["engine.session_step_flush"] == pytest.approx(300e-6)
    assert idle["scheduler.schedule"] == pytest.approx(200e-6)
    assert idle["device: between ops"] == pytest.approx(10e-6)
    assert idle["host: other"] == pytest.approx(180e-6)
    assert dict(r.top_ops)["fusion.1"] == pytest.approx(110e-6)


def test_recorded_tpu_trace_reduction():
    """80 ms of a real trace (tiny cell, one v5e) around an admission
    prefill: the reduction agrees with a plain rasterized count, and the
    idle time during the prefill belongs to the scheduler."""
    import gzip
    raw = json.loads(gzip.open(BENCH / "tests/data/tiny_tpu_trace.json.gz",
                               "rt").read())["events"]
    events = [trace.Event(*row) for row in raw]
    r = trace.reduce_events(events, harness.SPANS)
    assert r.window_s == pytest.approx(0.08) and r.chips == 1
    # busy time, counted again on a 100 ns grid
    grid = np.zeros(800_001, bool)
    for e in events:
        if trace.is_device_plane(e.plane) and e.line == trace.OPS_LINE:
            a, b = int(max(e.start_ns, 0) // 100), int(min(e.end_ns, 8e7) // 100)
            grid[a:b] = True
    assert r.busy_s == pytest.approx(grid.sum() * 1e-7, rel=1e-3)
    idle = dict(r.idle_by_host)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert max(idle, key=idle.get) == "scheduler.schedule"
    assert r.module_time("chunk_prefill_paged")[1] == 14
    assert r.module_time("fused_session_tick")[1] == 1
    assert 0 < r.idle_share < 1
