"""Program spans (:mod:`repro.core.trace`): one served tick of the
benchmark's dashboard round emits the span tree ``docs/metrics.md`` lists,
and the spans reach a profiler trace's host plane on the CPU backend."""

from __future__ import annotations

import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest

from repro.core import (RelationalMemoryEngine, RelationalTable,
                        benchmark_schema, operators, plan, trace)
from repro.serve import QueryServer

N_S, N_R = 512, 64
RANGE_ROWS = 128  # rows per kernel call the engine is held to: 4 row ranges


class Recorder:
    """Stands in for :func:`repro.core.trace.span`: records every span
    with its arguments and the span open around it."""

    def __init__(self):
        self.spans: list[_Span] = []
        self.stack: list[_Span] = []

    def __call__(self, name, **args):
        return _Span(self, name, {k: v for k, v in args.items()
                                  if v is not None})

    def named(self, name):
        return [s for s in self.spans if s.name == name]


class _Span:
    def __init__(self, rec, name, args):
        self.rec, self.name, self.args, self.parent = rec, name, args, None

    def __enter__(self):
        self.parent = self.rec.stack[-1] if self.rec.stack else None
        self.rec.stack.append(self)
        self.rec.spans.append(self)
        return self

    def __exit__(self, *exc):
        assert self.rec.stack.pop() is self
        return False

    def set_metadata(self, **more):
        self.args.update(more)

    def ancestors(self):
        s = self.parent
        while s is not None:
            yield s
            s = s.parent


@pytest.fixture
def tables():
    rng = np.random.default_rng(5)
    schema = benchmark_schema(64, 4)
    cols = {c.name: rng.integers(-1000, 1000, N_S).astype(np.int32)
            for c in schema.columns}
    bcols = {c.name: rng.integers(-1000, 1000, N_R).astype(np.int32)
             for c in schema.columns}
    bcols["A2"] = 2 * np.arange(N_R, dtype=np.int32)
    return (RelationalTable.from_columns(schema, cols),
            RelationalTable.from_columns(schema, bcols))


def _round(table, build):
    """The benchmark's dashboard round (Q0-Q5 as eight reads)."""
    q = lambda: plan(table)  # noqa: E731
    return [
        q().sum("A1"),
        q().project("A2"),
        q().project("A1", "A3"),
        q().project("A1", "A2", "A3", "A4"),
        q().filter("A3", "gt", 7).project("A5"),
        q().filter("A6", "lt", -3).sum("A4"),
        q().filter("A5", "lt", 11).groupby("A6", "A1", "avg", 64),
        q().join(build, key="A2", left_proj="A4", right_proj="A3"),
    ]


def _serve_round(monkeypatch, tables):
    """One served tick of the round with the span helper recorded; the
    engine cuts the table into row ranges as it would on a chip."""
    rec = Recorder()
    monkeypatch.setattr(trace, "span", rec)
    monkeypatch.setattr(RelationalMemoryEngine, "_kernel_row_limit",
                        lambda self, words, widths, block_rows: RANGE_ROWS)
    operators.clear_join_build_cache()
    server = QueryServer(RelationalMemoryEngine())
    tickets = [server.submit(p) for p in _round(*tables)]
    server.finish_tick(server.begin_tick())
    for tk in tickets:
        tk.result(timeout=60)
    return rec, tickets


def test_served_tick_emits_the_span_tree(monkeypatch, tables):
    rec, tickets = _serve_round(monkeypatch, tables)
    names = Counter(s.name for s in rec.spans)
    reads = len(tickets)
    ranges = -(-N_S // RANGE_ROWS)
    assert names["server.tick"] == 1 and names["server.finish_tick"] == 1
    assert names["engine.execute_many"] == 1
    assert names["engine.serve_scan"] == 1
    for per_read in ("planner.compile_plan", "server.launch",
                     "server.finalize"):
        assert names[per_read] == reads
    # one fused pass over the row ranges, and the join probe over the
    # pass's packed block cut into the same ranges
    assert names["engine.scan_multi"] == ranges
    assert names["engine.hash_join"] == ranges
    assert names["engine.row_slice"] == 2 * ranges
    assert names["engine.finish_join"] == 1
    assert set(names) <= {
        "server.tick", "server.finish_tick", "server.writes",
        "server.launch", "server.finalize", "planner.compile_plan",
        "engine.execute_many", "engine.serve_scan", "engine.row_slice",
        "engine.scan_multi", "engine.scan_solo", "engine.hash_join",
        "engine.combine", "engine.derive_covered", "engine.finish_join",
        "engine.join_direct"}
    # layer boundaries only: a few spans a read and a row range, never a
    # span per tile or per row
    assert len(rec.spans) <= 4 * reads + 4 * ranges + 8


def test_span_nesting_and_shared_ticket_args(monkeypatch, tables):
    rec, tickets = _serve_round(monkeypatch, tables)
    (tick,) = rec.named("server.tick")
    (finish,) = rec.named("server.finish_tick")
    assert tick.parent is None and finish.parent is None
    assert tick.args == {"tick": 1} and finish.args == {"tick": 1}
    (em,) = rec.named("engine.execute_many")
    assert em.parent is tick and em.args == {"tick": 1, "ops": len(tickets)}
    (scan,) = rec.named("engine.serve_scan")
    assert scan.parent is em
    assert scan.args["table"] == tables[0].uid
    # the fused pass's requests, after subsumption folded the covered ones
    (combine,) = [s for s in rec.named("engine.combine")
                  if s.parent is scan]
    assert combine.args == {"requests": scan.args["requests"],
                            "ranges": -(-N_S // RANGE_ROWS)}
    for s in rec.spans:
        if s.name.startswith("engine.") and s is not em:
            assert em in s.ancestors(), s.name
    for s in rec.named("engine.scan_multi"):
        assert s.parent is scan
        assert s.args["rows"] == RANGE_ROWS
    assert [s.args["range"] for s in rec.named("engine.scan_multi")] == [
        0, 1, 2, 3]
    for s in rec.named("engine.hash_join"):
        assert s.parent.name == "engine.finish_join"
        # the byte-plane contraction's width: whole 128-lane tiles
        assert s.args["lanes"] > 0 and s.args["lanes"] % 128 == 0
    for s in rec.named("planner.compile_plan"):
        assert s.parent is tick
        assert s.args["route"]
    # every span of one read carries the same (tick, ticket)
    by_ticket: dict[int, list[str]] = {}
    for s in rec.spans:
        if "ticket" in s.args:
            assert s.args["tick"] == 1
            by_ticket.setdefault(s.args["ticket"], []).append(s.name)
    assert sorted(by_ticket) == sorted(tk.id for tk in tickets)
    for tk in tickets:
        assert tk.tick == 1
        assert sorted(by_ticket[tk.id]) == [
            "planner.compile_plan", "server.finalize", "server.launch"]
    routes = {s.args["ticket"]: s.args["route"]
              for s in rec.named("planner.compile_plan")}
    assert routes == {tk.id: tk.route for tk in tickets}
    # express reads finalize inside the tick, bulk reads in finish_tick
    for s in rec.named("server.finalize"):
        assert s.parent is (tick if s.args["lane"] == "express" else finish)


def test_writes_are_spans_of_their_tick(monkeypatch, tables):
    rec = Recorder()
    monkeypatch.setattr(trace, "span", rec)
    table, _ = tables
    server = QueryServer(RelationalMemoryEngine())
    w = server.submit_insert(table, {c.name: np.zeros(4, np.int32)
                                     for c in table.schema.columns})
    r = server.submit(plan(table).sum("A1"))
    server.run_tick()
    r.result(timeout=60)
    (write,) = rec.named("server.writes")
    assert write.parent.name == "server.tick"
    assert write.args == {"tick": 1, "ticket": w.id, "lane": "express"}
    assert r.id == w.id + 1


def test_span_helper_is_a_trace_annotation():
    sp = trace.span("engine.combine", ranges=3, requests=None)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp as entered:  # inert without a profiler session
        entered.set_metadata(extra=1)


def test_spans_reach_the_profiler_trace(tables, tmp_path):
    """A ``jax.profiler.trace`` around a served tick holds the program's
    spans in the host plane, with their arguments as event stats."""
    operators.clear_join_build_cache()
    table, build = tables
    server = QueryServer(RelationalMemoryEngine())
    warm = server.submit(plan(table).sum("A1"))  # compiles outside the trace
    server.run_tick()
    warm.result(timeout=60)
    tks = [server.submit(plan(table).sum("A1")),
           server.submit(plan(table).project("A2"))]
    with jax.profiler.trace(str(tmp_path)):
        server.run_tick()
    for tk in tks:
        tk.result(timeout=60)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = [(e.name, dict(e.stats)) for p in data.planes
              if p.name == "/host:CPU" for line in p.lines
              for e in line.events]
    by_name: dict[str, list[dict]] = {}
    for name, stats in events:
        by_name.setdefault(name, []).append(stats)
    assert {"server.tick", "planner.compile_plan", "engine.execute_many",
            "server.finalize"} <= set(by_name)
    compiled = by_name["planner.compile_plan"]
    assert sorted(s["ticket"] for s in compiled) == sorted(
        tk.id for tk in tks)
    assert all(s["tick"] == 2 and s["route"] for s in compiled)
    (em,) = by_name["engine.execute_many"]
    assert em == {"tick": 2, "ops": 2}
