"""The server's own trace: host spans (``jax.profiler.TraceAnnotation``)
around each phase of ``SpecServer.step`` and inside the paged engine, and
``jax.named_scope`` stages inside the fused tick and the prefill chunk
program (docs/serving.md, "Tracing a server")."""
import glob
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import make_controller
from repro.core.engine import EngineSpec, _chunk_schedule
from repro.core.spec_decode import chunk_prefill_paged, fused_session_tick
from repro.serving.engine import SpecServer

CHUNK = 16
PROMPT_LENS = (21, 40, 9, 34)
BOUNDARIES = ("engine.session_step_flush", "server.release_finished",
              "scheduler.schedule", "engine.session_step_launch")


def _server(pair):
    draft, target = pair
    ctrl = make_controller("tapout_seq_ucb1", gamma_max=4, seed=0)
    return SpecServer(draft, target, ctrl, spec=EngineSpec(
        backend="paged", batch_size=2, max_len=256, block_size=8,
        prefill_chunk=CHUNK, fused=True, seed=0))


def _prompts():
    rng = np.random.default_rng(7)
    return [rng.integers(1, 60, size=n).tolist() for n in PROMPT_LENS]


def _serve(srv):
    for p in _prompts():
        srv.submit(p, 6)
    launched = []
    launch = srv.engine.session_step_launch

    def counted():
        launched.append(launch())
        return launched[-1]
    srv.engine.session_step_launch = counted
    srv.run_until_drained(timeout_s=600)
    tokens = {r.request_id: list(r.result.tokens) for r in srv.responses}
    return tokens, sum(launched)


class Span:
    def __init__(self, ev):
        self.name, self.stats = ev.name, dict(ev.stats)
        self.start, self.end = ev.start_ns, ev.start_ns + ev.duration_ns

    def inside(self, other) -> bool:
        return other.start <= self.start and self.end <= other.end


@pytest.fixture(scope="module")
def served(tiny_dense_pair, tmp_path_factory):
    """The same requests served twice by fresh servers: once with no
    profiler session (which also compiles every program), once under
    ``jax.profiler.start_trace``.  Returns both runs' tokens, the traced
    server, its launch count and its host spans."""
    plain, _ = _serve(_server(tiny_dense_pair))
    srv = _server(tiny_dense_pair)
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    try:
        traced, launches = _serve(srv)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    names = set(BOUNDARIES) | {"server.step", "server.admit",
                               "engine.prefill_chunk", "engine.flush_wait",
                               "engine.launch_dispatch"}
    spans = [Span(ev) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events if ev.name in names]
    return plain, traced, srv, launches, spans


def test_host_spans_count_and_nest(served):
    """(a) One ``server.admit`` per admission with its arguments, one
    ``engine.prefill_chunk`` per window of each model's chunk schedule, one
    ``engine.launch_dispatch`` and one ``engine.flush_wait`` per launched
    tick, and every new span inside its boundary span."""
    _, traced, srv, launches, spans = served
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    prompts = _prompts()
    assert len(by["server.step"]) == srv.tick_count
    admits = sorted(by["server.admit"], key=lambda s: s.stats["rid"])
    assert [s.stats["rid"] for s in admits] == sorted(traced)
    for s, p in zip(admits, prompts):
        assert s.stats["prompt_tokens"] == len(p)
        assert s.stats["adopted_tokens"] == 0
        assert s.stats["queue_wait_us"] >= 0
    windows = [hi - lo for p in prompts
               for lo, hi in _chunk_schedule(len(p) - 1, CHUNK)]
    chunks = Counter((s.stats["model"], s.stats["tokens"])
                     for s in by["engine.prefill_chunk"])
    for model in (0, 1):
        assert Counter({(model, n): k for n, k in Counter(windows).items()}) \
            == Counter({k: v for k, v in chunks.items() if k[0] == model})
    assert launches > 0
    assert len(by["engine.launch_dispatch"]) == launches
    assert len(by["engine.flush_wait"]) == launches
    for name, parent in (("engine.flush_wait", "engine.session_step_flush"),
                         ("engine.launch_dispatch",
                          "engine.session_step_launch"),
                         ("server.admit", "scheduler.schedule"),
                         ("engine.prefill_chunk", "server.admit")):
        for s in by[name]:
            assert any(s.inside(p) for p in by[parent]), (name, parent)
    for name in BOUNDARIES:
        assert by[name]
        for s in by[name]:
            assert any(s.inside(p) for p in by["server.step"]), name


def test_device_scopes_in_lowered_programs(served):
    """(b) The fused tick's ops carry the ``draft``, ``signals`` (inside
    ``draft``), ``verify``, ``accept`` (inside ``verify``) and ``rollback``
    scopes in their ``op_name`` metadata; the prefill chunk program's
    forward carries ``prefill``."""
    eng = served[2].engine
    B, g = eng.batch_size, eng.gamma_max
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    lens = jnp.zeros((B,), jnp.int32)
    tick = fused_session_tick.lower(
        eng.draft.params, eng.target.params, eng.draft.cfg, eng.target.cfg,
        eng.dspec, eng.tspec, eng.dcache, eng.tcache,
        jnp.zeros((B, 2), jnp.int32), jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B, g), jnp.int32), jnp.float32(0.0), keys, keys,
        jnp.zeros((B,), bool), lens, lens, lens, arms=eng.controller.arms,
        gamma_max=g, temperature=0.0, greedy=True, n_prompt_tokens=2,
        paged=True).as_text(dialect="hlo", debug_info=True)
    for scope in ("/rollback/", "/draft/", "/verify/", "/verify/accept/",
                  "/signals/"):
        assert f'op_name="jit(fused_session_tick)' in tick
        assert any(scope in line for line in tick.splitlines()
                   if "op_name=" in line), scope
    assert all("/draft/" in line for line in tick.splitlines()
               if "/signals/" in line)
    prefill = chunk_prefill_paged.lower(
        eng.target.params, eng.target.cfg, eng.tspec,
        eng._lane_view(eng.tcache, 0), jnp.zeros((1, CHUNK), jnp.int32),
        CHUNK).as_text(dialect="hlo", debug_info=True)
    assert 'op_name="jit(chunk_prefill_paged)/prefill/' in prefill


def test_tokens_identical_with_and_without_profiler(served):
    """(c) A profiler session changes nothing the server computes."""
    plain, traced = served[0], served[1]
    assert len(plain) == len(PROMPT_LENS)
    assert traced == plain
