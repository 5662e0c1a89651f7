"""The program's spans against the device's idle time, in a traced window.

The server, planner and engine mark each layer boundary with a span
(``repro.core.trace``: ``server.tick``, ``planner.compile_plan``,
``engine.execute_many`` and the engine's per-range children; the tree is in
``docs/metrics.md``).  The spans are ``TraceAnnotation``s, so a profiler
trace holds them on the host thread that served the ticks, on the same clock
as the device's operations.  This module puts each instant of the root
chip's idle time inside the benchmark's ``bench.traced`` span down to one
layer, by the spans open on the serving thread at that instant:

* ``idle_dispatch_ms_per_tick`` — an ``engine.*`` span is open: the engine
  is dispatching (``core/engine.py``);
* ``idle_frontend_ms_per_tick`` — a ``server.*`` or ``planner.*`` span is
  open, no engine span: the front end and the planner
  (``serve/query_server.py``, ``core/planner.py``);
* ``idle_between_ticks_ms_per_tick`` — no program span is open: the client's
  turn-around, the server's idle poll, a sampled answer's pull
  (``bench/clients``);

each over the traced ticks.  The three partition the idle time, so they sum
to ``device_idle_share`` / 100 x window / ticks.  ``plan_ms_per_read`` is
the summed ``planner.compile_plan`` time over the compiles that began in the
window.  Beside them, ``span_ms_per_tick`` and ``span_count_per_tick`` map
every span name on the serving thread to its summed time (inclusive of the
spans nested in it) and its count over the spans that began in the window,
per tick, so a reader of a new span needs no edit here.

The harness hands :func:`metrics` of its traced window to the readers
(``bench/metrics/<name>.py``, ``Traced.spans``), so a ``--trace 1`` run
prints the four numbers.  This module's script runs one traced window of a
cell through the harness and reduces the same trace further::

    python3 bench/spans.py --workload rm64.analytic --seed 7 --seconds 51

It prints the harness's result line with a ``spans`` object added: the four
numbers, the idle time they partition and its split by innermost span, the
longest idle gaps with the innermost program span open in each, and the
eager dispatches (``PjitFunction`` calls) each span issued.  ``--trace-seconds`` traces longer than the
harness's few rounds; ``--keep FILE`` writes the trace, xz-compressed.
``python3 bench/spans.py --span-cost`` prints instead what one span costs
the serving thread, without and with a profiler session.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python gets to it

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import reduce  # noqa: E402

PROGRAM_PREFIXES = ("server.", "planner.", "engine.")
DISPATCH_PREFIX = "engine."
TICK_SPAN = "server.tick"
COMPILE_SPAN = "planner.compile_plan"
EAGER_CALL = "PjitFunction"
EAGER_EVENTS = (EAGER_CALL, "DevicePut")
BENCH_WINDOW = "bench.traced"


@dataclasses.dataclass(frozen=True)
class Span:
    """One host event: a program span, or a runtime event beside it."""

    name: str
    start_ns: float
    end_ns: float
    args: tuple = ()  # (key, value) pairs

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


class SpanIndex:
    """The program spans of one thread, which nest, indexed by start: the
    innermost span open at an instant is found by bisection and a walk up
    its few ancestors."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: (s.start_ns, -s.end_ns))
        self.starts = [s.start_ns for s in self.spans]
        self.parent: list[int] = []
        stack: list[int] = []
        for i, s in enumerate(self.spans):
            while stack and self.spans[stack[-1]].end_ns <= s.start_ns:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def innermost(self, t: float) -> Span | None:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i].end_ns < t:
            i = self.parent[i]
        return self.spans[i] if i >= 0 else None


@dataclasses.dataclass
class ServingThread:
    """What a trace holds of the thread that served the ticks: its program
    spans, and its eager calls and transfers (:data:`EAGER_EVENTS`),
    sorted by start."""

    spans: SpanIndex
    eager: list[Span]


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def serving_thread(xspace) -> ServingThread:
    """The host line that holds ``server.tick`` spans; with none, every
    line's program spans (an engine driven without the server)."""
    lines = [line for plane in xspace.planes
             if plane.name == reduce.HOST_PLANE for line in plane.lines]
    picked = None
    for line in lines:
        if any(e.name == TICK_SPAN for e in line.events):
            picked = [line]
            break
    spans, eager = [], []
    for line in picked or lines:
        for e in line.events:
            if is_program(e.name):
                spans.append(Span(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  tuple(dict(e.stats).items())))
            elif picked is not None and e.name.startswith(EAGER_EVENTS):
                eager.append(Span(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    eager.sort(key=lambda s: (s.start_ns, -s.end_ns))
    return ServingThread(SpanIndex(spans), eager)


# ------------------------------------------------------------ intervals
def merge(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, t in sorted(intervals):
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def length(intervals) -> float:
    return sum(t - s for s, t in intervals)


def overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        t = min(a[i][1], b[j][1])
        if t > s:
            total += t - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_intervals(trace: reduce.Trace, lo: float, hi: float) -> list:
    """The root chip's operations inside ``[lo, hi]``, merged."""
    return merge(reduce._clip(reduce.root_ops(trace), lo, hi))


def idle_intervals(busy, lo: float, hi: float) -> list:
    out, cur = [], lo
    for s, t in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


# ------------------------------------------------------------ the split
def idle_split(spans: list[Span], busy, lo: float, hi: float) -> dict:
    """Idle nanoseconds of the window by the layer whose span was open."""
    idle = idle_intervals(busy, lo, hi)
    engine = merge((s.start_ns, s.end_ns) for s in spans
                   if s.name.startswith(DISPATCH_PREFIX))
    program = merge((s.start_ns, s.end_ns) for s in spans)
    in_engine = overlap(idle, engine)
    in_program = overlap(idle, program)
    total = length(idle)
    return {"dispatch_ns": in_engine, "frontend_ns": in_program - in_engine,
            "between_ticks_ns": total - in_program, "idle_ns": total}


def span_totals(spans: list[Span], lo: float,
                hi: float) -> dict[str, tuple[float, int]]:
    """Each span name's summed duration in nanoseconds and its count, over
    the spans that begin inside ``[lo, hi]``.  A span's duration is
    inclusive: the time of the spans nested in it is counted in it too."""
    ns: dict[str, float] = defaultdict(float)
    count: Counter = Counter()
    for s in spans:
        if lo <= s.start_ns <= hi:
            ns[s.name] += s.dur_ns
            count[s.name] += 1
    return {name: (ns[name], count[name]) for name in ns}


def metrics(spans: list[Span], busy, lo: float, hi: float,
            ticks: int) -> dict:
    """The four per-layer numbers of one traced window, and for each span
    name on the serving thread its time and its count per tick
    (``span_ms_per_tick``, ``span_count_per_tick``; :func:`span_totals`)."""
    if not ticks:
        return {}
    split = idle_split(spans, busy, lo, hi)
    totals = span_totals(spans, lo, hi)
    out = {
        "idle_dispatch_ms_per_tick": split["dispatch_ns"] * 1e-6 / ticks,
        "idle_frontend_ms_per_tick": split["frontend_ns"] * 1e-6 / ticks,
        "idle_between_ticks_ms_per_tick":
            split["between_ticks_ns"] * 1e-6 / ticks,
        "span_ms_per_tick": {name: ns * 1e-6 / ticks
                             for name, (ns, _) in totals.items()},
        "span_count_per_tick": {name: n / ticks
                                for name, (_, n) in totals.items()},
    }
    if COMPILE_SPAN in totals:
        ns, n = totals[COMPILE_SPAN]
        out["plan_ms_per_read"] = ns * 1e-6 / n
    return out


def idle_by_span(index: SpanIndex, idle) -> dict[str, float]:
    """Idle nanoseconds by the innermost program span open (``"none"``
    where none is): the idle split, one span deeper."""
    bounds = sorted({b for s in index.spans for b in (s.start_ns, s.end_ns)})
    out: Counter = Counter()
    for s, t in idle:
        cuts = ([s] + bounds[bisect.bisect_right(bounds, s):
                             bisect.bisect_left(bounds, t)] + [t])
        for a, b in zip(cuts, cuts[1:]):
            span = index.innermost((a + b) / 2)
            out[span.name if span else "none"] += b - a
    return dict(out.most_common())


def longest_gaps(index: SpanIndex, host, busy, lo: float, hi: float,
                 n: int = 10) -> list[dict]:
    """The ``n`` longest idle gaps, each with the innermost program span
    open at its middle (and its arguments), the shortest other host event
    (``host``: :class:`bench.reduce.Event`) that spans the middle, and the
    names of all that do, outermost first."""
    gaps = sorted(idle_intervals(busy, lo, hi), key=lambda g: g[0] - g[1])[:n]
    mids = [(s + t) / 2 for s, t in gaps]
    under: list[list] = [[] for _ in gaps]
    for e in host:
        if is_program(e.name) or e.name.startswith("bench."):
            continue
        for i, mid in enumerate(mids):
            if e.start_ns <= mid <= e.end_ns:
                under[i].append(e)
    out = []
    for (s, t), mid, events in zip(gaps, mids, under):
        span = index.innermost(mid)
        events.sort(key=lambda e: -e.dur_ns)
        out.append({
            "seconds": (t - s) * 1e-9,
            "at_s": (s - lo) * 1e-9,
            "span": span.name if span else None,
            "args": dict(span.args) if span else {},
            "runtime": events[-1].name if events else None,
            "host_events": [e.name for e in events],
        })
    return out


def dispatches(thread: ServingThread, lo: float, hi: float) -> dict:
    """Eager calls (outermost ``PjitFunction`` events, one per program the
    device runs) and ``DevicePut`` transfers the serving thread issued in
    the window, by the innermost program span open at each:
    ``by_span`` maps a span to ``[programs, transfers]``."""
    by_span: dict[str, list[int]] = {}
    by_call: Counter = Counter()
    outer_end = -1.0
    for e in thread.eager:
        if not lo <= e.start_ns <= hi:
            continue
        if e.name.startswith(EAGER_CALL):
            if e.start_ns < outer_end:
                continue  # a call nested in another: one program
            outer_end = e.end_ns
        span = thread.spans.innermost(e.start_ns)
        where = span.name if span else "no span"
        counts = by_span.setdefault(where, [0, 0])
        counts[0 if e.name.startswith(EAGER_CALL) else 1] += 1
        by_call[f"{where} {e.name}"] += 1
    return {"by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1][0])),
            "by_call": dict(by_call.most_common(20))}


def reduce_spans(xspace, ticks: int, n_gaps: int = 10) -> dict:
    """Everything this module reads of one traced window of the harness."""
    tr = reduce.load_trace(xspace)
    window = tr.annotation(BENCH_WINDOW)
    lo, hi = window.start_ns, window.end_ns
    thread = serving_thread(xspace)
    spans = thread.spans.spans
    busy = busy_intervals(tr, lo, hi)
    split = idle_split(spans, busy, lo, hi)
    by_span = idle_by_span(thread.spans, idle_intervals(busy, lo, hi))
    return {
        "metrics": metrics(spans, busy, lo, hi, ticks),
        "ticks": ticks,
        "window_s": (hi - lo) * 1e-9,
        "idle_s": split["idle_ns"] * 1e-9,
        "idle_ms_per_tick_by_span": {
            k: v * 1e-6 / max(ticks, 1) for k, v in by_span.items()},
        "spans": sum(lo <= s.start_ns <= hi for s in spans),
        "gaps": longest_gaps(thread.spans, tr.host, busy, lo, hi, n_gaps),
        "dispatches": dispatches(thread, lo, hi),
    }


# ------------------------------------------------------------ the script
def span_cost_us(n: int = 20_000) -> dict:
    """Microseconds to enter and leave one span with three arguments, as
    the engine's per-range spans are, without and with a profiler session
    as the harness opens one (no Python tracer); the mean of ``n``."""
    import shutil
    import tempfile

    import jax

    from repro.core import trace

    def mean_us():
        t = time.perf_counter()
        for i in range(n):
            with trace.span("engine.scan_multi", chunk=0, range=i,
                            rows=1 << 20):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = mean_us()
    trace_dir = tempfile.mkdtemp(prefix="span_cost_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        on = mean_us()
        jax.profiler.stop_trace()
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {"spans": n, "off_us": off, "on_us": on}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--span-cost", action="store_true",
                    help="print what one span costs, and run no cell")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="trace whole rounds for at least this long")
    ap.add_argument("--keep", default=None,
                    help="write the trace here, xz-compressed")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    import glob
    import lzma
    import os

    import jax

    from bench import harness

    args = parse_args(argv)
    if args.span_cost:
        print(json.dumps(span_cost_us()), flush=True)
        return 0
    if None in (args.workload, args.seed, args.seconds):
        print("bench: --workload, --seed and --seconds name the run",
              file=sys.stderr)
        return 2
    if args.trace_seconds is not None:
        harness.TRACE_SECONDS = args.trace_seconds
    found: dict = {}
    reduce_trace = harness._reduce_trace

    def with_spans(trace_dir, recs, *rest):
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        raw = pathlib.Path(path).read_bytes()
        if args.keep:
            pathlib.Path(args.keep).parent.mkdir(parents=True, exist_ok=True)
            pathlib.Path(args.keep).write_bytes(lzma.compress(raw))
        traced = reduce_trace(trace_dir, recs, *rest)
        found["spans"] = reduce_spans(
            jax.profiler.ProfileData.from_serialized_xspace(raw),
            traced.ticks)
        return traced

    harness._reduce_trace = with_spans
    try:
        result = harness.run(args.workload, args.seed, args.seconds, True,
                             t0=T0)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    spans = found["spans"]
    for g in spans["gaps"]:
        print(f"idle gap {g['seconds']} s at {g['at_s']} s: in {g['span']} "
              f"{json.dumps(g['args'])}, runtime {g['runtime']}",
              file=sys.stderr, flush=True)
    result["spans"] = spans
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
