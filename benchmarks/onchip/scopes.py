"""The server's own trace, reduced by its host spans and device scopes.

The server writes host spans (``jax.profiler.TraceAnnotation``:
``server.step`` and the spans nested in it) and names the stages of its
device programs (``jax.named_scope``: ``draft``, ``verify``, ``accept``,
``rollback``, ``prefill``); docs/serving.md, "Tracing a server", lists
them.  This module reduces a kept ``.xplane.pb`` by them, over the window
the harness marks with ``trace.WINDOW_SPAN``:

* ``scope_s``: device seconds under each scope, the union of the intervals
  of the operations whose ``op_name`` path holds it;
* ``idle_incl_s``: device idle seconds under each host span, the spans
  nested in it included;
* ``span_n``: spans begun in the window, by name;
* per execution of the fused tick, the draft's and the verifier's device
  time and the host gap (idle under ``server.step``, less that under
  ``server.admit``); per admission, the decode stall: the gaps between
  consecutive fused ticks that hold a ``server.admit`` span made while
  lanes were decoding.

    python3 benchmarks/onchip/scopes.py --workload <cell> --seed <n> \\
        --seconds <s> --out <dir>

runs the cell once, traced, as ``run.py --trace 1`` does, keeps the trace
under ``<dir>`` and prints its reduction as one JSON line last;
``--file <x.xplane.pb>`` reduces a kept trace.  Needs a TPU to run a cell.
The harness's per-layer readers do not read these numbers (PERF.md, Open
questions).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if __package__:
    from . import trace
else:                                   # run as a script
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "artifacts" / "jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT / "benchmarks"))
    from onchip import trace

SCOPES = ("draft", "verify", "accept", "rollback", "prefill")
PROGRAM_SPANS = ("server.step", "engine.session_step_flush",
                 "engine.flush_wait", "server.release_finished",
                 "scheduler.schedule", "server.admit", "engine.prefill_chunk",
                 "engine.session_step_launch", "engine.launch_dispatch")
# what JAX itself writes around an XLA compilation
COMPILE_SPANS = ("backend_compile_and_load", "backend_compile")
TICK = "fused_session_tick"


class Event(NamedTuple):
    """``trace.Event`` with the operation's scope path (device operations)
    or the span's arguments (program spans)."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""
    args: dict = {}

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


# ---------------------------------------------------- op_name metadata
#
# The device operation events carry no ``op_name``.  The profiler keeps
# each executed program's ``HloProto`` on the ``/host:metadata`` plane,
# one event metadata per program, named as the ``XLA Modules`` events are
# (``jit_f(<program id>)``).  The protobuf wire format is read directly:
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map: value = 2);
# XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6;
# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
# .metadata = 7; OpMetadata.op_name = 2.

METADATA_PLANE = "/host:metadata"


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = sh = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << sh
        if c < 0x80:
            return r, i
        sh += 7


def _fields(b: bytes, lo: int, hi: int):
    """(field number, value) of a message in ``b[lo:hi]``; a
    length-delimited value is its ``(start, end)``."""
    i = lo
    while i < hi:
        tag, i = _varint(b, i)
        wt = tag & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wt in (1, 5):
            n = 8 if wt == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield tag >> 3, v


def _sub(b: bytes, span: Tuple[int, int], field: int):
    return (v for f, v in _fields(b, *span) if f == field)


def _text(b: bytes, span: Tuple[int, int]) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Program name (``jit_f(5)``) -> HLO instruction name -> its
    ``op_name`` metadata, from the HLO protos kept in the trace."""
    b = Path(path).read_bytes()
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(b, (0, len(b)), 1):
        name = next((_text(b, v) for v in _sub(b, plane, 2)), "")
        if name != METADATA_PLANE:
            continue
        for entry in _sub(b, plane, 4):
            for meta in _sub(b, entry, 2):
                prog = next((_text(b, v) for v in _sub(b, meta, 2)), "")
                ops = out.setdefault(prog, {})
                for stat in _sub(b, meta, 5):
                    for proto in _sub(b, stat, 6):
                        for module in _sub(b, proto, 1):
                            for comp in _sub(b, module, 3):
                                for ins in _sub(b, comp, 2):
                                    _instruction(b, ins, ops)
    return out


def _instruction(b: bytes, ins: Tuple[int, int], ops: Dict[str, str]) -> None:
    name = op_name = ""
    for f, v in _fields(b, *ins):
        if f == 1:
            name = _text(b, v)
        elif f == 7:
            op_name = next((_text(b, x) for x in _sub(b, v, 2)), "")
    if name and op_name:
        ops[name] = op_name


def hlo_name(event_name: str) -> str:
    """``%fusion.3 = bf16[8] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def load_events(path: str) -> List[Event]:
    """Every event of an ``.xplane.pb``, with the scope path of each device
    operation (the ``op_name`` of its instruction in the program running
    at that moment on its chip) and the arguments of each program span."""
    from jax.profiler import ProfileData
    names = op_names(path)
    spans = set(PROGRAM_SPANS)
    out: List[Event] = []
    for plane in ProfileData.from_file(str(path)).planes:
        dev = trace.is_device_plane(plane.name)
        mods: List[Tuple[float, float, str]] = []
        ops: List[Event] = []
        for line in plane.lines:
            for ev in line.events:
                args = (dict(ev.stats) if not dev and ev.name in spans
                        else {})
                e = Event(plane.name, line.name, ev.name, float(ev.start_ns),
                          float(ev.duration_ns), "", args)
                if dev and line.name == trace.MODULES_LINE:
                    mods.append((e.start_ns, e.end_ns, ev.name))
                (ops if dev and line.name == trace.OPS_LINE else out).append(e)
        mods.sort()
        starts = [m[0] for m in mods]
        for e in ops:
            k = bisect.bisect_right(starts, e.start_ns) - 1
            if k >= 0 and e.start_ns < mods[k][1]:
                sc = names.get(mods[k][2], {}).get(hlo_name(e.name), "")
                e = e._replace(scope=sys.intern(sc))
            out.append(e)
    return out


def window(events: Sequence[Event]) -> Tuple[float, float]:
    """The ``trace.WINDOW_SPAN`` span, else the whole trace."""
    win = [e for e in events if e.name == trace.WINDOW_SPAN
           and not trace.is_device_plane(e.plane)]
    if win:
        return win[0].start_ns, win[0].end_ns
    return (min(e.start_ns for e in events), max(e.end_ns for e in events))


def _first_plane(events: Sequence[Event]) -> str:
    planes = sorted({e.plane for e in events if trace.is_device_plane(e.plane)
                     and e.line == trace.OPS_LINE})
    if not planes:
        raise ValueError("no device operations in the trace")
    return planes[0]


def _measure(iv: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in iv)


def _intersect(a: List[Tuple[float, float]],
               b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def scope_s(events: Sequence[Event], lo: float, hi: float,
            scopes: Sequence[str] = SCOPES) -> Dict[str, float]:
    """Device seconds under each scope on the first chip: the union of the
    intervals of the operations whose ``op_name`` path has the scope as one
    of its parts (``.../verify/accept/...`` counts for both)."""
    plane = _first_plane(events)
    iv: Dict[str, list] = {s: [] for s in scopes}
    for e in events:
        if e.plane == plane and e.line == trace.OPS_LINE and e.scope:
            parts = e.scope.split("/")
            for s in scopes:
                if s in parts:
                    iv[s].append((e.start_ns, e.end_ns))
    return {s: _measure(trace.union_ns(trace._clip(v, lo, hi))) / 1e9
            for s, v in iv.items()}


def device_gaps(events: Sequence[Event], lo: float,
                hi: float) -> List[Tuple[float, float]]:
    """The first chip's idle intervals in the window, each at least
    ``trace.MIN_GAP_NS`` long (shorter ones lie between the operations of
    one program)."""
    plane = _first_plane(events)
    busy = trace.union_ns(trace._clip(
        [(e.start_ns, e.end_ns) for e in events
         if e.plane == plane and e.line == trace.OPS_LINE], lo, hi))
    gaps, cur = [], lo
    for s, e in busy + [(hi, hi)]:
        if s - cur >= trace.MIN_GAP_NS:
            gaps.append((cur, s))
        cur = max(cur, e)
    return gaps


def _spans(events: Sequence[Event], name: str, lo: float,
           hi: float) -> List[Tuple[float, float]]:
    return trace.union_ns(trace._clip(
        [(e.start_ns, e.end_ns) for e in events if e.name == name
         and not trace.is_device_plane(e.plane)], lo, hi))


def idle_incl_s(events: Sequence[Event], lo: float, hi: float,
                names: Sequence[str]) -> Dict[str, float]:
    """Device idle seconds while each named span runs: under it or under
    any span nested in it."""
    gaps = device_gaps(events, lo, hi)
    return {n: _measure(_intersect(gaps, _spans(events, n, lo, hi))) / 1e9
            for n in names}


def span_n(events: Sequence[Event], lo: float, hi: float) -> Dict[str, int]:
    """Host spans begun in the window, by name."""
    return dict(Counter(e.name for e in events
                        if not trace.is_device_plane(e.plane)
                        and lo <= e.start_ns < hi))


def ticks(events: Sequence[Event], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Executions of the fused tick on the first chip that begin in the
    window, in order."""
    plane = _first_plane(events)
    return sorted((e.start_ns, e.end_ns) for e in events
                  if e.plane == plane and e.line == trace.MODULES_LINE
                  and TICK in e.name and lo <= e.start_ns < hi)


def admission_gaps(events: Sequence[Event], lo: float,
                   hi: float) -> List[Tuple[float, int, int]]:
    """The gaps between consecutive fused ticks that hold an admission
    made while lanes were decoding (its ``server.step`` began with
    ``active`` > 0): what those lanes wait while prompts are admitted (the
    prefill programs, the lane copies, and their dispatch).  Each gap as
    (seconds, admissions, prompt tokens)."""
    host = [e for e in events if not trace.is_device_plane(e.plane)]
    steps = [e for e in host if e.name == "server.step"]
    admits = []
    for e in host:
        if e.name == "server.admit" and any(
                s.start_ns <= e.start_ns < s.end_ns
                and s.args.get("active", 1) > 0 for s in steps):
            admits.append((e.start_ns, e.args.get("prompt_tokens", 0)))
    tk = ticks(events, lo, hi)
    out = []
    for (_, a), (b, _) in zip(tk, tk[1:]):
        held = [p for t, p in admits if a <= t < b]
        if held:
            out.append(((b - a) / 1e9, len(held), sum(held)))
    return out


def reduce(events: Sequence[Event], harness_spans: Sequence[str] = ()) -> dict:
    """The window's reduction by the program's spans and scopes."""
    lo, hi = window(events)
    if not any(e.name == trace.WINDOW_SPAN for e in events):
        events = list(events) + [Event("/host:CPU", "", trace.WINDOW_SPAN,
                                       lo, hi - lo)]
    names = tuple(dict.fromkeys(PROGRAM_SPANS + tuple(harness_spans)
                                + COMPILE_SPANS))
    base = trace.reduce_events(events, names, top=len(names) + 2)
    tk = ticks(events, lo, hi)
    n_tick = len(tk)
    sc = scope_s(events, lo, hi)
    idle = idle_incl_s(events, lo, hi, PROGRAM_SPANS + COMPILE_SPANS)
    n = span_n(events, lo, hi)
    tick_s, _ = base.module_time(TICK)

    def per_tick(s):
        # None where the program writes no such scope or span
        return 1e3 * s / n_tick if n_tick and s else None
    gaps = admission_gaps(events, lo, hi)
    admits = sum(g[1] for g in gaps)
    return {
        "window_s": base.window_s, "busy_s": base.busy_s,
        "ticks": n_tick, "tick_device_ms": per_tick(tick_s),
        "draft_ms_per_tick": per_tick(sc["draft"]),
        "verify_ms_per_tick": per_tick(sc["verify"]),
        "host_gap_ms_per_tick": per_tick(idle["server.step"]
                                         - idle["server.admit"]),
        "admission_stall_ms": (1e3 * sum(g[0] for g in gaps) / admits
                               if admits else None),
        "admission_gaps": gaps,
        "scope_s": sc, "idle_incl_s": idle,
        "span_n": {k: v for k, v in n.items() if k in names},
        "idle_gaps": base.idle_by_host,
        "module_s": dict(sorted(base.module_s.items(), key=lambda kv: -kv[1])[:12]),
        "module_calls": base.module_calls,
    }


def top_ops_by_scope(events: Sequence[Event], lo: float, hi: float,
                     top: int = 12) -> List[Tuple[str, str, float]]:
    """The heaviest device operations in the window, each with its scope
    path: (label, scope, seconds), nested operations counted in full."""
    plane = _first_plane(events)
    acc: Dict[Tuple[str, str], float] = defaultdict(float)
    for e in events:
        if e.plane == plane and e.line == trace.OPS_LINE:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                acc[(trace.op_label(e.name), e.scope)] += (t - s) / 1e9
    return [(k[0], k[1], v) for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--file", help="a kept .xplane.pb to reduce")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", help="where the run keeps its trace")
    args = ap.parse_args(argv)
    from onchip import harness
    extra = {}
    if args.file:
        path = args.file
    else:
        if not (args.workload and args.out):
            ap.error("give --file, or --workload and --out")
        import glob
        import jax
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            print(f"scopes.py: needs a TPU, found {dev.platform!r}",
                  file=sys.stderr)
            return 2
        cell = harness.load_cell(args.workload, ROOT)
        out = harness.run_cell(cell, args.seed, args.seconds, True,
                               t_process=T_PROCESS, chip_kind=dev.device_kind,
                               trace_dir=args.out,
                               log=lambda m: print(m, flush=True))
        extra = {"metrics": {k: v["value"] for k, v in
                             out.line["metrics"].items()},
                 "correct": out.line["correct"], "device": out.line["device"]}
        [path] = glob.glob(f"{args.out}/**/*.xplane.pb", recursive=True)
    t = time.perf_counter()
    events = load_events(path)
    red = reduce(events, harness.SPANS)
    lo, hi = window(events)
    red["top_ops"] = top_ops_by_scope(events, lo, hi, top=24)
    red["reduce_s"] = time.perf_counter() - t
    print(json.dumps({**extra, **red}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
