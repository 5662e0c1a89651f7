"""QueryServer: concurrent admission, per-tick shared scans, stats, errors."""

import threading

import numpy as np
import pytest

from repro.core import RelationalMemoryEngine, RelationalTable, benchmark_schema, plan
from repro.serve import QueryServer

GROUPS = (("A1",), ("A1", "A2", "A3", "A4"), ("A1", "A3"), ("A2", "A4"))


@pytest.fixture
def table():
    rng = np.random.default_rng(0)
    schema = benchmark_schema(64, 4)
    n = 400
    return RelationalTable.from_columns(
        schema,
        {c.name: rng.integers(-100, 100, n).astype(np.int32)
         for c in schema.columns},
    )


def test_concurrent_same_table_queries_share_one_scan(table):
    """N clients, same table, one tick: exactly one shared scan, one upload."""
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    tickets = {}
    barrier = threading.Barrier(len(GROUPS))

    def client(i, cols):
        barrier.wait()  # all clients submit concurrently
        tickets[i] = server.submit(plan(table).project(*cols), client=f"c{i}")

    threads = [threading.Thread(target=client, args=(i, g))
               for i, g in enumerate(GROUPS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert server.queue_depth == len(GROUPS)

    served = server.run_tick()
    assert served == len(GROUPS)
    assert eng.stats.shared_scans == 1  # one pass served every client
    assert eng.stats.uploads == 1  # the row store crossed host->device once
    assert server.stats.shared_scan_ratio == 1.0
    assert server.stats.bytes_saved > 0

    solo = RelationalMemoryEngine()
    for i, cols in enumerate(GROUPS):
        expect = solo.register(table, cols).packed()
        np.testing.assert_array_equal(
            np.asarray(tickets[i].result(timeout=5)), np.asarray(expect)
        )


def test_mixed_kinds_one_tick(table):
    """Aggregates, group-bys, and projections coexist in one coalesced tick."""
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    t_agg = server.submit(plan(table).filter("A4", "lt", 5).sum("A2"))
    t_proj = server.submit(plan(table).project("A1", "A3"))
    t_gb = server.submit(plan(table).groupby("A2", "A1", "avg", 16))
    server.run_tick()
    assert t_agg.route == "fused-aggregate"
    assert t_proj.route == "rme"
    assert t_gb.route == "fused-groupby"
    s, _ = eng.aggregate(table, "A2", "A4", "lt", 5)
    assert t_agg.result(timeout=5) == s
    assert t_gb.result(timeout=5).shape == (16,)


def test_two_tables_two_shared_scans(table):
    rng = np.random.default_rng(1)
    other = RelationalTable.from_columns(
        table.schema,
        {c.name: rng.integers(-5, 5, 64).astype(np.int32)
         for c in table.schema.columns},
    )
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    for tab in (table, other):
        for cols in (("A1", "A2"), ("A2", "A5")):
            server.submit(plan(tab).project(*cols))
    server.run_tick()
    assert eng.stats.shared_scans == 2  # one coalesced pass per table
    assert eng.stats.uploads == 2
    assert server.stats.table_groups == 2
    assert server.stats.shared_scan_ratio == 1.0


def test_second_tick_is_hot(table):
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    for cols in GROUPS:
        server.submit(plan(table).project(*cols))
    server.run_tick()
    scans = eng.stats.shared_scans
    for cols in GROUPS:
        server.submit(plan(table).project(*cols))
    server.run_tick()
    assert eng.stats.shared_scans == scans  # reorg cache absorbed the repeat
    assert eng.stats.hot_hits >= len(GROUPS)
    assert server.stats.table_groups == 1  # the hot tick opened no cold group


def test_max_batch_bounds_a_tick(table):
    server = QueryServer(RelationalMemoryEngine(), max_batch=3)
    tks = [server.submit(plan(table).project("A1")) for _ in range(7)]
    assert server.run_tick() == 3
    assert server.queue_depth == 4
    assert server.drain() == 4
    for tk in tks:
        assert tk.done()


def test_errors_resolve_their_ticket_only(table):
    server = QueryServer(RelationalMemoryEngine())
    bad = server.submit(plan(table).project("A1").filter("missing", "gt", 0))
    good = server.submit(plan(table).sum("A1"))
    server.run_tick()
    with pytest.raises(KeyError):
        bad.result(timeout=5)
    assert isinstance(good.result(timeout=5), float)
    assert server.stats.failed == 1 and server.stats.served == 1


def test_shared_step_failure_resolves_every_ticket(table):
    """If the coalesced materialize_many itself raises, every ticket in the
    batch must resolve with the error — a hung result() (and a silently dead
    background loop) is the failure mode being guarded."""
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)

    def boom(ops):
        raise RuntimeError("union geometry failed to lower")

    eng.execute_many = boom
    tks = [server.submit(plan(table).project(*g)) for g in GROUPS]
    assert server.run_tick() == len(GROUPS)
    for tk in tks:
        assert tk.done()
        with pytest.raises(RuntimeError, match="union geometry"):
            tk.result(timeout=1)
    assert server.stats.failed == len(GROUPS) and server.stats.served == 0


def test_background_serving_thread(table):
    eng = RelationalMemoryEngine()
    with QueryServer(eng) as server:
        tickets = [
            server.submit(plan(table).project(*GROUPS[i % len(GROUPS)]),
                          client=f"c{i % 2}")
            for i in range(8)
        ]
        results = [tk.result(timeout=30) for tk in tickets]
    assert all(r is not None for r in results)
    assert server.stats.lanes["bulk"].latency.count == 8
    assert sorted(tk.id for tk in tickets) == list(range(1, 9))
    assert all(tk.tick >= 1 for tk in tickets)
    snap = server.snapshot()
    assert snap["served"] == 8 and snap["queue_depth"] == 0
    assert snap["max_latency_s"] >= snap["mean_latency_s"] > 0


def test_served_join_shares_scans(table):
    rng = np.random.default_rng(9)
    n_r = 64
    r_cols = {c.name: rng.integers(-50, 50, n_r).astype(np.int32)
              for c in table.schema.columns}
    r_cols["A2"] = np.arange(n_r, dtype=np.int32)
    rt = RelationalTable.from_columns(table.schema, r_cols)
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    from repro.core import operators as ops

    ops.clear_join_build_cache()
    tk = server.submit(
        plan(table).join(rt, key="A2", left_proj="A1", right_proj="A3")
    )
    server.run_tick()
    res = tk.result(timeout=5)
    ref = ops.q5_hash_join(RelationalMemoryEngine(), table, rt)
    np.testing.assert_array_equal(np.asarray(res.matched),
                                  np.asarray(ref.matched))
    np.testing.assert_array_equal(np.asarray(res.r_proj),
                                  np.asarray(ref.r_proj))


# ---------------------------------------------------------------------------
# Pipelined serving: lanes, deadlines, backpressure, streaming, reservoirs
# ---------------------------------------------------------------------------

def _cols(seed, n, schema):
    rng = np.random.default_rng(seed)
    return {c.name: rng.integers(-100, 100, n).astype(np.int32)
            for c in schema.columns}


def test_express_completes_while_bulk_in_flight(table):
    """begin_tick serves express tickets to completion while the bulk lane's
    (same fused) pass is still awaiting finish_tick."""
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    t_bulk = server.submit(plan(table).project("A1", "A2", "A3"))
    t_exp = server.submit(plan(table).sum("A1"))
    assert t_exp.lane == "express" and t_bulk.lane == "bulk"

    tick = server.begin_tick()
    assert t_exp.done() and not t_bulk.done()
    assert isinstance(t_exp.result(timeout=1), float)

    assert server.finish_tick(tick) == 2
    assert t_bulk.done()
    # lanes share one fused pass — the one-pass-per-tick invariant holds
    assert eng.stats.shared_scans == 1
    snap = server.snapshot()
    assert snap["express_served"] == 1 and snap["bulk_served"] == 1
    assert snap["express_p99_ms"] > 0 and snap["bulk_p99_ms"] > 0


def test_deadline_missed_fails_typed_not_hung(table):
    """An expired ticket resolves promptly with DeadlineExceeded (a
    TimeoutError) — and healthy co-tick tickets are unaffected."""
    from repro.serve import DeadlineExceeded

    server = QueryServer(RelationalMemoryEngine())
    doomed = server.submit(plan(table).project("A1"), deadline_s=0.0)
    fine = server.submit(plan(table).project("A2"))
    server.run_tick()
    assert doomed.done()
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=1)
    assert isinstance(DeadlineExceeded("x"), TimeoutError)
    assert fine.result(timeout=5) is not None
    snap = server.snapshot()
    assert snap["deadline_misses"] == 1
    assert snap["bulk_deadline_misses"] == 1
    assert server.stats.failed == 1 and server.stats.served == 1


def _mixed_workload(server, t, other):
    return [
        server.submit(plan(t).project("A1", "A3")),
        server.submit(plan(t).filter("A5", "gt", 10).project("A1", "A2")),
        server.submit(plan(t).sum("A2")),
        server.submit(plan(t).groupby("A2", "A1", "avg", 16)),
        server.submit(plan(other).project("A2", "A4")),
        server.submit(plan(other).filter("A4", "lt", 5).sum("A1")),
    ]


@pytest.mark.parametrize("backend", ["single", "sharded"])
def test_overlapped_ticks_match_serial(table, backend):
    """Pipelined (double-buffered) drain is byte-identical to serial ticks,
    on both backends."""
    def mk_engine():
        if backend == "sharded":
            from repro.core.distributed import ShardedEngine
            return ShardedEngine(num_shards=3, revision="xla")
        return RelationalMemoryEngine()

    def run(pipeline):
        t = RelationalTable.from_columns(
            table.schema, _cols(3, 300, table.schema))
        other = RelationalTable.from_columns(
            table.schema, _cols(4, 200, table.schema))
        # max_batch=2 forces several ticks, so the pipelined drain overlaps
        server = QueryServer(mk_engine(), max_batch=2, pipeline=pipeline)
        tickets = _mixed_workload(server, t, other)
        assert server.drain() == len(tickets)
        return [tk.result(timeout=30) for tk in tickets], server

    serial, _ = run(pipeline=False)
    piped, server = run(pipeline=True)
    assert server.stats.ticks_overlapped > 0  # it really double-buffered
    for i, (a, b) in enumerate(zip(serial, piped)):
        fa = a if isinstance(a, tuple) else (a,)
        fb = b if isinstance(b, tuple) else (b,)
        for x, y in zip(fa, fb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), f"query {i}"


@pytest.mark.parametrize("backend", ["single", "sharded"])
def test_streamed_chunks_concat_to_blocking_result(table, backend):
    """A streamed projection's chunks concatenate to exactly the blocking
    result, and arrive as more than one piece."""
    if backend == "sharded":
        from repro.core.distributed import ShardedEngine
        engine = ShardedEngine(num_shards=3, revision="xla")
    else:
        engine = RelationalMemoryEngine()
    t = RelationalTable.from_columns(table.schema, _cols(5, 400, table.schema))
    server = QueryServer(engine)

    blocking = server.submit(plan(t).project("A1", "A4"))
    server.drain()
    expect = np.asarray(blocking.result(timeout=30))

    # fresh server+engine so the stream runs cold, not from the warm cache
    if backend == "sharded":
        engine = ShardedEngine(num_shards=3, revision="xla")
    else:
        engine = RelationalMemoryEngine()
    t2 = RelationalTable.from_columns(table.schema, _cols(5, 400, table.schema))
    server = QueryServer(engine)
    tk = server.submit(plan(t2).project("A1", "A4"), stream=True,
                       stream_chunk_rows=64)
    from repro.serve import StreamingTicket
    assert isinstance(tk, StreamingTicket)
    server.drain()
    chunks = list(tk.chunks(timeout=5))
    assert len(chunks) > 1
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c) for c in chunks]), expect)
    np.testing.assert_array_equal(np.asarray(tk.result(timeout=5)), expect)
    snap = server.snapshot()
    assert snap["streams"] == 1
    assert snap["stream_chunks"] == len(chunks)


def test_stream_yields_chunks_before_resolution(table):
    """chunks() observes early chunks while the pass is still in flight:
    after begin_tick the stream is launched but unresolved."""
    server = QueryServer(RelationalMemoryEngine())
    tk = server.submit(plan(table).project("A1"), stream=True,
                       stream_chunk_rows=64)
    tick = server.begin_tick()
    assert not tk.done()  # launched, not finalized
    server.finish_tick(tick)
    assert tk.done()
    assert len(list(tk.chunks(timeout=1))) > 1


def test_stream_of_written_table_fails_honestly(table):
    """A streamed read of a table this server has written compiles with the
    tick snapshot, which the stream contract cannot carry — the ticket must
    fail with PlanError, never return unversioned rows."""
    from repro.core.plan import PlanError

    t = RelationalTable.from_columns(table.schema, _cols(6, 100, table.schema))
    server = QueryServer(RelationalMemoryEngine())
    server.submit_delete(t, np.array([0, 1]))
    tk = server.submit(plan(t).project("A1"), stream=True)
    server.drain()
    with pytest.raises(PlanError):
        tk.result(timeout=5)


def test_backpressure_shed_at_bound(table):
    from repro.serve import ServerOverloaded

    server = QueryServer(RelationalMemoryEngine(), max_queue=4)
    tks = [server.submit(plan(table).project("A1")) for _ in range(4)]
    with pytest.raises(ServerOverloaded):
        server.submit(plan(table).project("A2"))
    assert server.stats.shed == 1
    server.drain()
    for tk in tks:
        assert tk.result(timeout=5) is not None


def test_backpressure_degrade_then_hard_shed(table):
    from repro.serve import ServerOverloaded

    server = QueryServer(RelationalMemoryEngine(), max_queue=2,
                         overload="degrade")
    server.submit(plan(table).sum("A1"))
    server.submit(plan(table).sum("A2"))
    # at the bound: demoted to bulk, deadline stripped, not refused
    demoted = server.submit(plan(table).sum("A3"), deadline_s=10.0)
    assert demoted.lane == "bulk" and demoted.deadline_s is None
    assert server.stats.degraded == 1
    server.submit(plan(table).sum("A4"))  # depth 4 == 2 * bound
    with pytest.raises(ServerOverloaded):  # hard shed keeps memory bounded
        server.submit(plan(table).sum("A5"))
    # writes are never degraded — refused outright at the bound
    with pytest.raises(ServerOverloaded):
        server.submit_insert(table, _cols(7, 4, table.schema))
    assert server.stats.shed == 2
    server.drain()


def test_lanes_off_restores_single_fifo(table):
    server = QueryServer(RelationalMemoryEngine(), lanes=False)
    tk = server.submit(plan(table).sum("A1"))
    assert tk.lane == "bulk"
    tick = server.begin_tick()
    assert not tk.done()  # no express fast path
    server.finish_tick(tick)
    assert isinstance(tk.result(timeout=5), float)


def test_latency_reservoir_exact_small_n():
    from repro.serve import LatencyReservoir

    r = LatencyReservoir(cap=512)
    values = list(range(1, 101))
    rng = np.random.default_rng(8)
    rng.shuffle(values)
    for v in values:
        r.add(float(v))
    assert r.count == 100
    assert r.sum == sum(range(1, 101))
    assert r.max == 100.0
    # nearest-rank percentiles are exact below the cap
    assert r.percentile(50) == 50.0
    assert r.percentile(95) == 95.0
    assert r.percentile(99) == 99.0
    assert r.percentile(100) == 100.0


def test_latency_reservoir_bounded_memory():
    from repro.serve import LatencyReservoir

    r = LatencyReservoir(cap=64)
    n = 100_000
    for i in range(n):
        r.add(float(i % 1000))
    assert r.count == n  # exact totals survive the sampling
    assert r.sum == sum(float(i % 1000) for i in range(n))
    assert r.max == 999.0
    assert len(r._samples) == 64  # memory stays at the cap
    assert 0.0 <= r.percentile(50) <= 999.0


def test_snapshot_back_compat_keys(table):
    """Historical snapshot/stat consumers keep working after the reservoir
    rework: mean/max read through the reservoir-backed properties."""
    server = QueryServer(RelationalMemoryEngine())
    server.submit(plan(table).project("A1"))
    server.drain()
    snap = server.snapshot()
    assert snap["max_latency_s"] >= snap["mean_latency_s"] > 0
    assert server.stats.latency_sum_s > 0
    assert server.stats.latency_max_s >= server.stats.latency_sum_s / 1
