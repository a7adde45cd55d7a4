"""One run of one benchmark cell: build the server, warm it up, measure a
window, check what the window served against the plain reference.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name from ``BENCHMARK.json``: the configuration's file
(``configs/<name>.json``), the traffic file (``traffic/<name>.json``, read
by ``gen/traffic.py``) and one reader per metric (``metrics/<name>.py``).
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import re
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import counts
from .gen.traffic import (Traffic, load_spec, make_traffic, pool_lengths,
                          seed_rng)

ROOT = Path(__file__).resolve().parents[2]

# host spans written into the trace around the calls into each layer
SPANS = ("bench.step", "engine.session_step_flush", "server.release_finished",
         "scheduler.schedule", "engine.session_step_launch", "client.poll",
         "client.submit", "client.idle")
WARMUP_TIMEOUT_S = 900.0


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a malformed file)."""


# ---------------------------------------------------------------- the cell

@dataclass
class Cell:
    """What ``BENCHMARK.json`` and the files it names say about one cell."""
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    check: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path
    bench_dir: Path

    def reader(self, metric: str) -> Callable:
        path = self.bench_dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise BenchError(f"no reader {path} for metric {metric!r}")
        spec = importlib.util.spec_from_file_location(
            "onchip_metric_" + re.sub(r"\W", "_", metric), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _for_cell(metrics: Sequence[dict], cell: str) -> List[dict]:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / bench["paths"][0]
    config = json.loads((root / cfgs[w["config"]]["file"]).read_text())
    traffic = load_spec(bench_dir / "traffic" / f"{w['traffic']}.json")
    # the limits of the cell's check, set from the cell's own readings
    check = json.loads((bench_dir / "checks" / f"{workload}.json").read_text())
    e2e = _for_cell(bench["end_to_end"], workload)
    return Cell(workload, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, check, e2e, _for_cell(bench["per_layer"], workload),
                root, bench_dir)


# ---------------------------------------------------------------- models

def _reference_module(config: dict):
    return importlib.import_module(
        f"{__package__}.reference.{config['reference']}")


def model_config(s, name: str):
    """The program's ``ModelConfig`` for the sizes ``s`` (a reference
    module's ``Shape``) of a dense decoder."""
    from repro.models import ModelConfig
    return ModelConfig(
        name=name, arch_type="dense", num_layers=s.layers, d_model=s.d,
        num_heads=s.heads, num_kv_heads=s.kv_heads, head_dim=s.head_dim,
        d_ff=s.d_ff, vocab_size=s.vocab, activation="swiglu",
        qk_norm=s.qk_norm, qkv_bias=s.qkv_bias, rope_theta=s.rope_theta,
        rms_eps=s.eps, tie_embeddings=True, block_pattern=("attn",))


def seed_words(seed: int, stream: int) -> Tuple[int, int]:
    a, b = seed_rng(seed, stream).integers(0, 2 ** 31 - 1, size=2)
    return int(a), int(b)


@dataclass
class Layout:
    """Lanes and pool of one cell.  The pool takes the configuration's
    KV-pool budget; each lane can hold the traffic's worst case (longest
    prompt + longest output + one tick's overshoot); and there are as many
    lanes as the pool holds requests of the traffic's mean reservation with
    the configuration's headroom, up to the traffic's lane cap, so that the
    scheduler's block-aware backpressure is rare but possible, as in a
    deployment sized to its memory."""
    lanes: int
    slot_tokens: int
    pool_tokens: int
    kv_bytes_per_token: int
    mean_reserve_tokens: float

    @classmethod
    def of(cls, config: dict, traffic: dict, target: counts.Dims,
           draft: counts.Dims) -> "Layout":
        srv = config["serving"]
        block, gamma = int(srv["block_size"]), int(srv["gamma_max"])
        worst = int(traffic["prompt"]["hi"]) + int(traffic["output"]["hi"]) + gamma + 2
        slot = -(-worst // block) * block
        per_tok = target.kv_bytes_per_token + draft.kv_bytes_per_token
        pool = int(float(srv["kv_pool_bytes"]) // (per_tok * block)) * block
        plens, olens = pool_lengths(traffic)
        reserve = float(np.mean(-(-(plens + olens + gamma + 2) // block) * block))
        cap = int(traffic.get("clients") or traffic["lanes"])
        lanes = min(cap, int(pool // (reserve * float(srv["reserve_headroom"]))))
        if lanes < 1 or pool < slot:
            raise BenchError("the KV-pool budget holds no worst-case lane")
        return cls(lanes, slot, pool, per_tok, reserve)


# ---------------------------------------------------------------- compile

class CompileClock:
    """Count and seconds of XLA backend compilations while open."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------- the loop

@dataclass
class ReqLog:
    rid: int
    index: int               # position in the traffic's request list
    prompt_len: int
    max_new: int
    due: float               # host clock: when the client meant to send it
    submitted: float
    count: int = 0           # tokens delivered so far
    first: Optional[float] = None
    done: Optional[float] = None
    admitted_step: Optional[int] = None
    sessions_seen: int = 0


@dataclass
class Record:
    """What the client side saw, on the benchmark's clock."""
    deliveries: List[Tuple[int, float, int, int]] = field(default_factory=list)
    #            (rid, time, tokens, context before them)
    ticks: List[Tuple[float, List[Tuple[int, int, int]]]] = field(default_factory=list)
    #            (time, [(ctx, drafted, accepted) per lane])
    admissions: List[Tuple[float, int]] = field(default_factory=list)
    #            (time, prompt length)
    steps: List[float] = field(default_factory=list)
    lateness: List[float] = field(default_factory=list)


class Driver:
    """Client side of one run: submits the traffic's requests (closed or
    open loop), calls ``SpecServer.step`` and timestamps, after each step,
    the tokens every request has gained."""

    def __init__(self, server, traffic: Traffic, annotate=None):
        self.server = server
        self.traffic = traffic
        self.logs: Dict[int, ReqLog] = {}
        self.rec = Record()
        self.next_index = 0
        self.n_seen_resp = 0
        self.annotate = annotate
        self.open_t0: Optional[float] = None
        self.due = traffic.due_s() if traffic.loop == "open" else None

    def _span(self, name):
        return self.annotate(name) if self.annotate else contextlib.nullcontext()

    def submit_next(self, due: float) -> None:
        i = self.next_index
        if i >= len(self.traffic.prompts):
            raise BenchError("the traffic ran out of requests; raise pool_size")
        self.next_index += 1
        prompt = self.traffic.prompts[i]
        now = time.perf_counter()
        rid = self.server.submit(prompt.tolist(), self.traffic.max_new[i])
        self.logs[rid] = ReqLog(rid, i, len(prompt), self.traffic.max_new[i],
                                due, now)
        self.rec.lateness.append(now - due)

    def start_open(self, t0: float) -> None:
        self.open_t0 = t0

    def _submit_due(self) -> None:
        now = time.perf_counter()
        while (self.next_index < len(self.due)
               and self.open_t0 + self.due[self.next_index] <= now):
            self.submit_next(self.open_t0 + self.due[self.next_index])

    def step(self) -> List[int]:
        srv = self.server
        if self.traffic.loop == "open":
            with self._span("client.submit"):
                self._submit_due()
            if not srv.queue and not srv.active:
                if self.next_index >= len(self.due):
                    raise BenchError("the traffic ran out of requests; "
                                     "raise pool_size")
                with self._span("client.idle"):
                    nxt = self.open_t0 + self.due[self.next_index]
                    time.sleep(max(0.0, nxt - time.perf_counter()))
                return []
        with self._span("bench.step"):
            finished = srv.step()
        now = time.perf_counter()
        with self._span("client.poll"):
            self._poll(now, finished)
        if self.traffic.loop == "closed":
            with self._span("client.submit"):
                for _ in finished:
                    self.submit_next(now)
        return finished

    def _poll(self, now: float, finished: Sequence[int]) -> None:
        srv = self.server
        self.rec.steps.append(now)
        states = {rid: st["res"] for rid, st in srv.active.items()}
        for r in srv.responses[self.n_seen_resp:]:
            states[r.request_id] = r.result
        self.n_seen_resp = len(srv.responses)
        lanes = []
        for rid, res in states.items():
            log = self.logs[rid]
            if log.admitted_step is None:
                log.admitted_step = len(self.rec.steps)
                self.rec.admissions.append((now, log.prompt_len))
            # one new session per lane per flushed tick
            for s in res.sessions[log.sessions_seen:]:
                ctx = log.prompt_len + log.count - 1
                lanes.append((ctx, int(s.n_drafted), int(s.n_accepted)))
            log.sessions_seen = len(res.sessions)
            n = res.new_tokens
            if n > log.count:
                self.rec.deliveries.append(
                    (rid, now, n - log.count, log.prompt_len + log.count - 1))
                if log.first is None:
                    log.first = now
                log.count = n
        if lanes:
            self.rec.ticks.append((now, lanes))
        for rid in finished:
            self.logs[rid].done = now


# ---------------------------------------------------------------- metrics

def percentile(values: Sequence[float], p: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), p)) if len(values) else None


def end_to_end(name: str, rec: Record, logs: Dict[int, ReqLog], t0: float,
               t1: float, setup_s: float) -> Optional[float]:
    """The end-to-end metrics, over the window ``(t0, t1]`` on the client's
    clock.  ``itl_pNN_ms`` and ``ttft_pNN_ms`` take their percentile from
    the name; ``itl_mean_ms`` and ``ttft_mean_ms`` are the means of the
    same samples."""
    if name == "setup_s":
        return setup_s
    if name == "output_tok_s":
        return sum(k for _, t, k, _ in rec.deliveries if t0 < t <= t1) / (t1 - t0)
    m = re.fullmatch(r"(itl|ttft)_(?:p(\d+)|mean)_ms", name)
    if m is None:
        raise BenchError(f"no definition of end-to-end metric {name!r}")
    samples = (itl_samples(rec, t0, t1) if m.group(1) == "itl"
               else ttft_samples(logs, t0, t1))
    if m.group(2) is None:
        v = float(np.mean(samples)) if samples else None
    else:
        v = percentile(samples, float(m.group(2)))
    return None if v is None else v * 1e3


def itl_samples(rec: Record, t0: float, t1: float) -> List[float]:
    """Every delivery in the window after a request's first: the time since
    that request's previous delivery (which may precede the window)."""
    prev: Dict[int, float] = {}
    out = []
    for rid, t, _, _ in rec.deliveries:
        if rid in prev and t0 < t <= t1:
            out.append(t - prev[rid])
        prev[rid] = t
    return out


def ttft_samples(logs: Dict[int, ReqLog], t0: float, t1: float) -> List[float]:
    """Requests whose first token falls in the window: due time to first
    delivered token."""
    return [lg.first - lg.due for lg in logs.values()
            if lg.first is not None and t0 < lg.first <= t1]


@dataclass
class RunView:
    """What a per-layer metric reader gets."""
    window_s: float
    rec: Record
    logs: Dict[int, ReqLog]
    t0: float
    t1: float
    target: counts.Dims
    draft: counts.Dims
    peak: dict
    trace: Optional[object] = None

    def in_window(self, t: float) -> bool:
        return self.t0 < t <= self.t1


# ---------------------------------------------------------------- checks

@dataclass
class CheckResult:
    numbers: Dict[str, Tuple[float, float]]   # name -> (value, limit)
    detail: dict

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.numbers.values())


def draw_sample(done: List[Tuple[int, int]], seed: int, min_tokens: int,
                max_requests: int) -> List[int]:
    """Request ids to compare: the one with the most served tokens, then
    others drawn from the seed until ``min_tokens`` served tokens or
    ``max_requests`` requests.  ``done`` holds (rid, served tokens)."""
    if not done:
        return []
    done = sorted(done)
    longest = max(done, key=lambda d: (d[1], -d[0]))
    rest = [d for d in done if d[0] != longest[0]]
    order = seed_rng(seed, 3).permutation(len(rest))
    pick, total = [longest[0]], longest[1]
    for i in order:
        if total >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(rest[i][0])
        total += rest[i][1]
    return pick


def served_gaps(ref, params, shape, seq: np.ndarray, prompt_len: int,
                pad_to: int, controls: Sequence[str] = ()) -> dict:
    """Widest gap, in units of the reference logits' standard deviation, by
    which a served token's reference logit lies below the reference's best;
    and, for each lower ``controls`` precision, the same gap of the token
    that precision puts first at each served position."""
    served = np.asarray(seq[prompt_len:], np.int32)
    picks = [served] + [ref.rows(params, shape, seq, prompt_len - 1, pad_to,
                                 precision=c).argmax for c in controls]
    st = ref.rows(params, shape, seq, prompt_len - 1, pad_to,
                  tokens=np.stack(picks, 1))
    gaps = (st.top[:, None] - st.at) / st.std
    out = {name: float(gaps[:, j].max())
           for j, name in enumerate(("program",) + tuple(controls))}
    out["ref_flips"] = int((st.argmax != served).sum())
    for j, name in enumerate(controls, 1):
        out[f"{name}_flips"] = int((st.argmax != picks[j]).sum())
    return out


# ---------------------------------------------------------------- the run

@dataclass
class RunOutput:
    line: dict
    stderr_tail: List[str]
    detail: dict


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, chip_kind: Optional[str] = None,
             on_server: Optional[Callable] = None,
             controls: Sequence[str] = (),
             trace_dir: Optional[str] = None,
             log: Callable[[str], None] = print) -> RunOutput:
    """One run of ``cell``.  ``chip_kind`` is the device kind the caller
    checked (None off the chip: no peaks, no device metrics).
    ``on_server`` may replace parts of the server before warm-up (the
    fault tests break the timed path through it); ``controls`` adds the
    lower-precision readings to the check (never in a benchmark run).
    A trace goes to a temporary directory and is deleted once reduced,
    unless ``trace_dir`` names where to keep it."""
    import jax
    import jax.numpy as jnp
    from repro.core import EngineSpec, ModelBundle, make_controller
    from repro.serving.engine import SpecServer

    ref = _reference_module(cell.config)
    tshape = ref.Shape.from_config(cell.config["model"])
    dshape = ref.Shape.from_config(cell.config["draft"])
    tdims, ddims = counts.Dims.of(tshape), counts.Dims.of(dshape)
    srv_cfg = cell.config["serving"]
    lay = Layout.of(cell.config, cell.traffic, tdims, ddims)
    gamma = int(srv_cfg["gamma_max"])
    log(f"layout: lanes={lay.lanes} slot_tokens={lay.slot_tokens} "
        f"pool_tokens={lay.pool_tokens} kv_bytes_per_token={lay.kv_bytes_per_token}")

    tparams = ref.make_params(tshape, seed_words(seed, 10), cell.config["weights"])
    dparams = ref.make_params(dshape, seed_words(seed, 11), cell.config["weights"])
    jax.block_until_ready((tparams, dparams))
    tcfg = model_config(tshape, cell.config_name)
    dcfg = model_config(dshape, cell.config_name + "-draft")
    ctrl = make_controller(srv_cfg["controller"], gamma_max=gamma,
                           seed=int(seed % (2 ** 31)))
    spec = EngineSpec(backend="paged", batch_size=lay.lanes,
                      pool_tokens=lay.pool_tokens,
                      block_size=int(srv_cfg["block_size"]),
                      max_len=lay.slot_tokens, cache_dtype=jnp.bfloat16,
                      prefill_chunk=int(srv_cfg["prefill_chunk"]),
                      fused=True, seed=int(seed % (2 ** 31)))
    server = SpecServer(ModelBundle(dparams, dcfg), ModelBundle(tparams, tcfg),
                        ctrl, spec=spec)
    if not (server.backend == "paged" and server.engine.fused):
        raise BenchError("expected the fused paged backend")
    if on_server is not None:
        on_server(server)
    traffic = make_traffic(cell.traffic_name, cell.traffic, seed, tshape.vocab)

    # warm-up: one short request per lane compiles the prefill chunk
    # programs (whole chunks and singles), the fused tick, admission into
    # and release from every slot
    chunk = int(srv_cfg["prefill_chunk"])
    wrng = seed_rng(seed, 4)
    clock = CompileClock().__enter__()
    for _ in range(lay.lanes):
        server.submit(wrng.integers(0, tshape.vocab, 2 * chunk + 2).tolist(), 1)
    server.run_until_drained(timeout_s=WARMUP_TIMEOUT_S)
    server.responses.clear()
    warm_compiles = clock.count

    annotate = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
        _wrap_layers(server, annotate)
    drv = Driver(server, traffic, annotate)
    start = time.perf_counter()
    if traffic.loop == "closed":
        for _ in range(lay.lanes):
            drv.submit_next(start)
        # every lane decoding before the window opens
        deadline = start + WARMUP_TIMEOUT_S
        while any(lg.first is None for lg in drv.logs.values()):
            if time.perf_counter() > deadline:
                raise BenchError("lanes still waiting for a first token "
                                 f"after {WARMUP_TIMEOUT_S} s")
            drv.step()
    else:
        drv.start_open(start)
        preroll = float(cell.traffic.get("preroll_s", 0.0))
        # every request due in the pre-roll is admitted in it, so that no
        # admission's prefill straddles the window's open
        n_pre = int(np.searchsorted(drv.due, preroll, side="right"))
        while (time.perf_counter() < start + preroll or drv.next_index < n_pre
               or any(lg.first is None for lg in drv.logs.values()
                      if lg.index < n_pre)):
            drv.step()
    jax.block_until_ready((server.engine.dcache, server.engine.tcache))
    compiles_before = clock.count

    tmp = trace_dir or (tempfile.mkdtemp(prefix="onchip_trace_") if trace else None)
    if trace:
        # host spans and device activity only: the Python tracer would add
        # an event per Python call, and its cost, to the window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    end = t0 + seconds
    with (annotate("bench.window") if trace else contextlib.nullcontext()):
        while time.perf_counter() < end:
            drv.step()
        jax.block_until_ready((server.engine.dcache, server.engine.tcache))
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_compiles = clock.count - compiles_before
    clock.__exit__(None, None, None)
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    lat = np.asarray(drv.rec.lateness[lay.lanes if traffic.loop == "closed" else 0:])
    log(f"setup: {setup_s:.3f} s; compilations: {warm_compiles} in warm-up, "
        f"{compiles_before - warm_compiles} in the pre-roll, "
        f"{window_compiles} inside the window")
    if lat.size and traffic.loop == "open":
        log(f"generator lateness: n={lat.size} p50={np.percentile(lat, 50) * 1e3:.3f} ms "
            f"p99={np.percentile(lat, 99) * 1e3:.3f} ms max={lat.max() * 1e3:.3f} ms")
    itl = np.asarray(itl_samples(drv.rec, t0, t1)) * 1e3
    if itl.size:
        log(f"itl: n={itl.size} mean={itl.mean():.3f} ms "
            + " ".join(f"p{q}={np.percentile(itl, q):.3f}" for q in (50, 95, 99))
            + f" ms max={itl.max():.3f} ms")
    stats = server.throughput_stats()
    in_window = [lg for lg in drv.logs.values()
                 if lg.done is not None and t0 < lg.done <= t1]
    log(f"window: {t1 - t0:.3f} s, {sum(1 for s in drv.rec.steps if t0 < s <= t1)} "
        f"steps, {len(in_window)} requests finished, "
        f"{sum(k for _, t, k, _ in drv.rec.deliveries if t0 < t <= t1)} tokens "
        f"delivered; server accept_rate over the run {stats.get('accept_rate')}")

    # what the window served: every request finished in it, whole, and
    # every request still in flight at its close, as far as it was served
    # (long outputs at a low acceptance may finish in no window at all)
    by_rid = {r.request_id: r for r in server.responses}
    served = {lg.rid: list(by_rid[lg.rid].result.tokens) for lg in in_window}
    served.update({rid: list(st["res"].tokens)
                   for rid, st in server.active.items()
                   if st["res"].new_tokens > 0})
    bad = []
    for rid, toks in served.items():
        lg = drv.logs[rid]
        out = np.asarray(toks[lg.prompt_len:])
        finished = rid in by_rid
        if (toks[:lg.prompt_len] != traffic.prompts[lg.index].tolist()
                or len(out) > lg.max_new + gamma
                or (finished and len(out) < lg.max_new)
                or not ((out >= 0) & (out < tshape.vocab)).all()):
            bad.append(rid)
    sample = draw_sample([(rid, len(t) - drv.logs[rid].prompt_len)
                          for rid, t in served.items()], seed,
                         int(cell.check["min_tokens"]),
                         int(cell.check["max_requests"]))
    seqs = [(np.asarray(served[r], np.int32), drv.logs[r].prompt_len)
            for r in sample]

    view = RunView(t1 - t0, drv.rec, drv.logs, t0, t1, tdims, ddims,
                   counts.peaks(chip_kind) if chip_kind else {})
    metrics: Dict[str, dict] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem}
    breakdown = None
    if trace:
        from . import trace as trace_mod
        import glob
        import shutil
        files = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        view.trace = trace_mod.reduce_file(files[0], SPANS)
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = view.trace.busy_s
        device["window_s"] = view.trace.window_s
        breakdown = {"device_ops": [[n, s] for n, s in view.trace.top_ops],
                     "idle_gaps": [[n, s] for n, s in view.trace.idle_by_host]}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            v = end_to_end(m["name"], drv.rec, drv.logs, t0, t1, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = len(drv.logs)
    load = {"queued_at_close": len(server.queue),
            "in_flight_at_close": len(server.active),
            "submitted_in_window": sum(1 for lg in drv.logs.values()
                                       if t0 < lg.submitted <= t1),
            "first_tokens": sorted((lg.first - t0, lg.first - lg.due)
                                   for lg in drv.logs.values()
                                   if lg.first is not None and t0 < lg.first <= t1)}

    # the reference runs once the program's state is freed
    del server, drv.server, dparams
    gc.collect()
    t_ref = time.perf_counter()
    pad_to = -(-lay.slot_tokens // 128) * 128
    gaps = [served_gaps(ref, tparams, tshape, s, p, pad_to, controls)
            for s, p in seqs]
    worst = {k: max(g[k] for g in gaps) for k in gaps[0]} if gaps else {}
    limit = float(cell.check["served_gap_limit"])
    numbers = {"served_gap": (worst.get("program", math.inf), limit),
               "bad_answers": (float(len(bad)), 0.0)}
    chk = CheckResult(numbers, {"sample": sample, "finished": sum(
        r in by_rid for r in sample), "served_tokens": int(sum(
        len(s) - p for s, p in seqs)), "gaps": worst,
        "reference_s": time.perf_counter() - t_ref})
    log(f"check: {len(sample)} requests, {chk.detail['served_tokens']} served "
        f"tokens compared in {chk.detail['reference_s']:.3f} s; "
        f"gaps {json.dumps(worst)}")
    line = {"correct": chk.correct, "attempted": attempted,
            "failed": len(bad), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in numbers.items()}
    tail = [f"check {k}: {v} (limit {lim})" for k, (v, lim) in numbers.items()]
    return RunOutput(line, tail, {"check": chk.detail, "layout": lay.__dict__,
                                  "load": load,
                                  "stats": {k: v for k, v in stats.items()
                                            if isinstance(v, (int, float))}})


def _wrap_layers(server, annotate) -> None:
    """Host spans around the calls into each layer, from benchmark code:
    instance attributes shadow the methods ``SpecServer.step`` calls."""
    def wrap(name, fn):
        def inner(*a, **kw):
            with annotate(name):
                return fn(*a, **kw)
        return inner
    eng = server.engine
    eng.session_step_flush = wrap("engine.session_step_flush",
                                  eng.session_step_flush)
    eng.session_step_launch = wrap("engine.session_step_launch",
                                   eng.session_step_launch)
    server.scheduler.schedule = wrap("scheduler.schedule",
                                     server.scheduler.schedule)
    server._release_finished = wrap("server.release_finished",
                                    server._release_finished)
