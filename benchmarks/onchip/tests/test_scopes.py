"""The reduction of a trace by the program's own spans and scopes
(``scopes.py``), on hand-made events; and the readers of the accepted
per-layer metrics, pinned on the recorded chip trace."""
import gzip
import json

import pytest

from conftest import BENCH, ROOT, tiny_config
from onchip import counts, harness, scopes, trace

DEV, HOST = "/device:TPU:0", "/host:CPU"
TICK_SCOPE = "jit(fused_session_tick)"


def _op(start_us, dur_us, scope="", name="fusion.1"):
    return scopes.Event(DEV, trace.OPS_LINE, name, start_us * 1e3,
                        dur_us * 1e3, scope)


def _span(name, start_us, dur_us, **args):
    return scopes.Event(HOST, "python", name, start_us * 1e3, dur_us * 1e3,
                        args=args)


def _module(name, start_us, dur_us):
    return scopes.Event(DEV, trace.MODULES_LINE, name, start_us * 1e3,
                        dur_us * 1e3)


def _events():
    """Two steps in a 2-ms window.  Step one admits a prompt: the tick
    before it runs at 100-400 us, its prefill chunks at 500-700 us and
    800-850 us, the next tick at 1000-1300 us; step two admits nothing and
    its tick runs at 1400-1700 us."""
    return [
        _span(trace.WINDOW_SPAN, 0, 2000),
        _span("server.step", 0, 1000, active=1, queued=1),
        _span("engine.session_step_flush", 0, 420),
        _span("engine.flush_wait", 10, 390),
        _span("server.release_finished", 420, 10),
        _span("scheduler.schedule", 430, 440),
        _span("server.admit", 440, 420, rid=3, prompt_tokens=40),
        _span("engine.prefill_chunk", 450, 200, model=0, tokens=16),
        _span("engine.prefill_chunk", 650, 200, model=1, tokens=16),
        _span("engine.session_step_launch", 870, 130),
        _span("engine.launch_dispatch", 950, 50),
        _span("server.step", 1000, 400, active=2, queued=0),
        _span("engine.session_step_flush", 1000, 320),
        _span("engine.flush_wait", 1000, 300),
        _span("engine.session_step_launch", 1320, 80),
        _span("engine.launch_dispatch", 1380, 20),
        _span("server.step", 2500, 100),                   # after the window
        _module("jit_fused_session_tick(1)", 100, 300),
        _op(100, 150, f"{TICK_SCOPE}/rollback/select"),
        _op(110, 140, f"{TICK_SCOPE}/draft/while", "while.1"),
        _op(120, 60, f"{TICK_SCOPE}/draft/while/body/dot_general"),
        _op(250, 140, f"{TICK_SCOPE}/verify/dot_general"),
        _op(350, 40, f"{TICK_SCOPE}/verify/accept/sort"),
        _op(390, 10, f"{TICK_SCOPE}/rollback/select"),
        _module("jit_chunk_prefill_paged(2)", 500, 200),
        _op(500, 200, "jit(chunk_prefill_paged)/prefill/while"),
        _op(800, 50, "dynamic_update_slice"),              # the lane merge
        _module("jit_fused_session_tick(1)", 1000, 300),
        _op(1000, 300, f"{TICK_SCOPE}/draft/while"),
        _module("jit_fused_session_tick(1)", 1400, 300),
        _op(1400, 300, f"{TICK_SCOPE}/verify/dot_general"),
    ]


def test_op_names_from_the_hlo_protos_in_a_trace(tmp_path):
    """A real profiler trace (CPU): the programs' HLO protos it keeps give
    each instruction its ``op_name``, named scopes included."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("draft"):
            y = jax.lax.fori_loop(0, 2, lambda i, a: jnp.tanh(a @ a), x)
        with jax.named_scope("verify"):
            return jnp.cos(y).sum()

    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    [path] = tmp_path.glob("**/*.xplane.pb")
    names = scopes.op_names(str(path))
    [prog] = [k for k in names if k.startswith("jit_f(")]
    paths = set(names[prog].values())
    assert any(p.startswith("jit(f)/draft/while/") for p in paths)
    assert any(p.startswith("jit(f)/verify/") for p in paths)
    assert scopes.hlo_name("%fusion.3 = bf16[8] fusion(%p), kind=kLoop") == "fusion.3"


def test_scope_seconds_union_and_nesting():
    ev = _events()
    s = scopes.scope_s(ev, 0, 2e6)
    # draft: 110-250 (the body op nested in the while), then 1000-1300
    assert s["draft"] == pytest.approx(140e-6 + 300e-6)
    # verify holds accept: 250-390, then 1400-1700
    assert s["verify"] == pytest.approx(140e-6 + 300e-6)
    assert s["accept"] == pytest.approx(40e-6)
    assert s["rollback"] == pytest.approx(150e-6 + 10e-6)
    assert s["prefill"] == pytest.approx(200e-6)
    # clipped to the window
    assert scopes.scope_s(ev, 0, 1.1e6)["draft"] == pytest.approx(240e-6)


def test_idle_under_nested_spans():
    ev = _events()
    # idle intervals: 0-100, 400-500, 700-800, 850-1000, 1300-1400, 1700-2000
    gaps = scopes.device_gaps(ev, 0, 2e6)
    assert [(a / 1e3, b / 1e3) for a, b in gaps] == [
        (0, 100), (400, 500), (700, 800), (850, 1000), (1300, 1400),
        (1700, 2000)]
    idle = scopes.idle_incl_s(ev, 0, 2e6, scopes.PROGRAM_SPANS)
    us = {k: round(v * 1e6, 6) for k, v in idle.items()}
    assert us["server.step"] == 100 + 100 + 100 + 150 + 100
    assert us["engine.session_step_flush"] == 100 + 20 + 20
    assert us["engine.flush_wait"] == 90
    assert us["scheduler.schedule"] == 70 + 100 + 20
    # the admission holds its chunks' idle time, and its own
    assert us["server.admit"] == 60 + 100 + 10
    assert us["engine.prefill_chunk"] == 50 + 100
    assert us["engine.session_step_launch"] == 130 + 80
    assert us["engine.launch_dispatch"] == 50 + 20


def test_span_counts_in_the_window():
    n = scopes.span_n(_events(), 0, 2e6)
    assert n["server.step"] == 2 and n["server.admit"] == 1
    assert n["engine.prefill_chunk"] == 2 and n["engine.launch_dispatch"] == 2


def test_admission_stall_is_the_tick_gap_that_holds_an_admission():
    ev = _events()
    assert [(a / 1e3, b / 1e3) for a, b in scopes.ticks(ev, 0, 2e6)] == [
        (100, 400), (1000, 1300), (1400, 1700)]
    # the gap 400-1000 holds server.admit; 1300-1400 holds none
    assert scopes.admission_gaps(ev, 0, 2e6) == [(pytest.approx(600e-6), 1, 40)]
    # an admission into an empty server stalls no lane
    idle = [e._replace(args=dict(e.args, active=0))
            if e.name == "server.step" else e for e in ev]
    assert scopes.admission_gaps(idle, 0, 2e6) == []
    assert scopes.reduce(idle, harness.SPANS)["admission_stall_ms"] is None


def test_reduce_per_tick_readings():
    r = scopes.reduce(_events(), harness.SPANS)
    assert r["ticks"] == 3
    assert r["tick_device_ms"] == pytest.approx(0.3)
    assert r["draft_ms_per_tick"] == pytest.approx(0.44 / 3)
    assert r["verify_ms_per_tick"] == pytest.approx(0.44 / 3)
    assert r["draft_ms_per_tick"] + r["verify_ms_per_tick"] <= r["tick_device_ms"]
    # idle under server.step less that under server.admit
    assert r["host_gap_ms_per_tick"] == pytest.approx((0.55 - 0.17) / 3)
    assert r["admission_stall_ms"] == pytest.approx(0.6)
    idle = dict(r["idle_gaps"])
    assert idle["host: other"] == pytest.approx(300e-6)     # 1700-2000
    assert idle["engine.flush_wait"] == pytest.approx(90e-6)


def test_a_trace_without_program_spans_reads_nothing():
    """A program that writes no scopes or program spans (one built before
    them): the per-tick readings are None, not zero, and nothing raises."""
    ev = [e._replace(scope="") for e in _events()
          if e.name not in scopes.PROGRAM_SPANS or e.name.startswith("engine.session")]
    r = scopes.reduce(ev, harness.SPANS)
    assert r["ticks"] == 3
    assert r["draft_ms_per_tick"] is None and r["verify_ms_per_tick"] is None
    assert r["host_gap_ms_per_tick"] is None and r["admission_stall_ms"] is None


# ------------------------------------- a recorded chip trace

def _recorded():
    raw = json.loads(gzip.open(BENCH / "tests/data/tiny_spans_trace.json.gz",
                               "rt").read())["events"]
    return [scopes.Event(*row) for row in raw]


def test_recorded_trace_spans_nest_and_account_for_idle():
    """80 ms of the tiny cell on one v5e around an admission, with the
    program's own spans and scopes: idle under a span holds the idle under
    the spans nested in it, every idle moment is attributed, the
    admission's chunks are counted, and each scope holds device time."""
    ev = _recorded()
    r = scopes.reduce(ev, harness.SPANS)
    assert r["window_s"] == pytest.approx(0.08) and r["ticks"] == 2
    idle = r["idle_incl_s"]
    for outer, inner in (("server.step", "scheduler.schedule"),
                         ("scheduler.schedule", "server.admit"),
                         ("server.admit", "engine.prefill_chunk"),
                         ("server.step", "engine.session_step_launch"),
                         ("engine.session_step_launch", "engine.launch_dispatch"),
                         ("engine.session_step_flush", "engine.flush_wait")):
        assert idle[outer] >= idle[inner] > 0, (outer, inner)
    # the admission's chunks hold most of the idle time in this slice
    assert idle["engine.prefill_chunk"] > 0.5 * (r["window_s"] - r["busy_s"])
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert gaps["host: other"] < 1e-4
    n = r["span_n"]
    assert n["server.admit"] == 1 and n["engine.prefill_chunk"] == 13
    # two ticks run in the slice; the first was dispatched before it began
    assert n["engine.launch_dispatch"] == 1
    [admit] = [e for e in ev if e.name == "server.admit"]
    assert admit.args["prompt_tokens"] > 0 and admit.args["adopted_tokens"] == 0
    # each device op carries its instruction's op_name path, and every
    # stage of the fused tick and the prefill chunk runs under its scope
    paths = {e.scope for e in ev if e.line == trace.OPS_LINE and e.scope}
    for prefix in ("jit(fused_session_tick)/draft/while/",
                   "jit(fused_session_tick)/verify/accept/",
                   "jit(fused_session_tick)/rollback/",
                   "jit(chunk_prefill_paged)/prefill/while/"):
        assert any(p.startswith(prefix) for p in paths), prefix
    sc = r["scope_s"]
    assert all(sc[s] > 0 for s in scopes.SCOPES)
    assert sc["accept"] < sc["verify"]
    assert (r["draft_ms_per_tick"] + r["verify_ms_per_tick"]
            <= r["tick_device_ms"])


# ------------------------------------- the accepted readers, pinned

def _old_fixture():
    raw = json.loads(gzip.open(BENCH / "tests/data/tiny_tpu_trace.json.gz",
                               "rt").read())["events"]
    return trace.reduce_events([trace.Event(*row) for row in raw], harness.SPANS)


def test_accepted_readers_unchanged_on_the_recorded_trace():
    """The four accepted per-layer metrics read what they read before the
    program wrote spans of its own: the recorded chip trace (harness spans
    only) and a fixed record give the values pinned here."""
    from onchip.reference.qwen_dense import Shape
    cfg = tiny_config()
    tdims = counts.Dims.of(Shape.from_config(cfg["model"]))
    ddims = counts.Dims.of(Shape.from_config(cfg["draft"]))
    rec = harness.Record()
    rec.admissions += [(0.01, 40), (0.05, 24)]
    for i in range(1, 9):
        for rid in (0, 1):
            rec.deliveries.append((rid, 0.01 * i + 0.003 * rid * (i % 3), 2, 30 + i))
    view = harness.RunView(0.08, rec, {}, 0.0, 0.08, tdims, ddims,
                           counts.peaks("TPU v5 lite"), _old_fixture())
    cell = harness.load_cell("qwen2.5-3b.rag_open", ROOT)
    got = {m["name"]: cell.reader(m["name"])(view) for m in cell.per_layer}
    assert got == pytest.approx(PINNED, rel=1e-9)


# what these readers returned before the program wrote spans of its own
PINNED = {"itl_p99_ms.admission": 13.00000000000001,
          "prefill_roofline": 0.06752018401871722,
          "mfu.rag": 0.00010547005076142132,
          "device_idle_share.rag": 98.66022625000001}


def test_a_trace_without_a_window_is_reduced_whole():
    ev = [e for e in _events() if e.name != trace.WINDOW_SPAN]
    r = scopes.reduce(ev, harness.SPANS)
    # from the first event (0 us) to the last (the step at 2500-2600 us)
    assert r["window_s"] == pytest.approx(2.6e-3)
    assert r["ticks"] == 3 and r["span_n"]["server.step"] == 3
