"""Heterogeneous one-pass scan: kernel, engine, executor, and server layers.

Cross-path equality: every request kind served by the fused pass must match
its single-op kernel and the ``ref.py`` oracle — across all revisions,
under padded (non-tile-multiple) row counts, and with the MVCC snapshot test
fused.  Plus the engine-level contracts: request de-duplication, union-
geometry byte accounting, the VMEM budget guard, and the serving-layer
guarantee that a mixed-kind same-table tick performs exactly one shared scan.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    AggregateOp,
    BatchExecutor,
    FilterOp,
    GroupByOp,
    ProjectOp,
    RelationalMemoryEngine,
    RelationalTable,
    TableGeometry,
    benchmark_schema,
    execute_batch,
    plan,
)
from repro.kernels import ref as R
from repro.kernels.ops import (
    REVISIONS,
    AggregateRequest,
    FilterRequest,
    GroupByRequest,
    ProjectRequest,
    aggregate,
    filter_project,
    groupby_sum,
    project_any,
    request_intervals,
    scan_multi,
    scan_vmem_footprint_bytes,
    union_geometry,
)
from repro.serve import QueryServer


def make_table(n=500, row_bytes=64, seed=0):
    rng = np.random.default_rng(seed)
    schema = benchmark_schema(row_bytes, 4)
    cols = {c.name: rng.integers(-100, 100, n).astype(np.int32)
            for c in schema.columns}
    return schema, RelationalTable.from_columns(schema, cols)


def mixed_requests(schema, n):
    g_proj = TableGeometry.from_schema(schema, ["A1", "A2", "A3", "A4"], n)
    g_filt = TableGeometry.from_schema(schema, ["A1", "A3"], n)
    return (
        ProjectRequest(g_proj),
        FilterRequest(g_filt, pred_word=4, pred_op="gt", pred_k=10),
        AggregateRequest(agg_word=1, pred_word=3, pred_op="lt", pred_k=5),
        GroupByRequest(group_word=1, agg_word=0, num_groups=8),
    )


# ------------------------------------------------------------ kernel layer
@pytest.mark.parametrize("revision", REVISIONS)
@pytest.mark.parametrize("n", [64, 777])  # tile-multiple and padded tails
def test_scan_multi_matches_solo_kernels_and_oracle(revision, n):
    schema, t = make_table(n)
    words = jnp.asarray(t.words())
    reqs = mixed_requests(schema, n)
    outs = scan_multi(words, reqs, revision=revision, block_rows=256)

    np.testing.assert_array_equal(
        np.asarray(outs[0]), np.asarray(R.project_ref(words, reqs[0].geom))
    )
    ref_pk, ref_m = R.filter_project_ref(
        words, reqs[1].geom, 4, "int32", "gt", 10
    )
    np.testing.assert_array_equal(np.asarray(outs[1][0]), np.asarray(ref_pk))
    np.testing.assert_array_equal(np.asarray(outs[1][1]), np.asarray(ref_m))
    ref_sum = R.aggregate_ref(words, 1, "int32", 3, "int32", "lt", 5)
    np.testing.assert_allclose(float(outs[2][0]), float(ref_sum), rtol=1e-5)
    ref_s, ref_c = R.groupby_sum_ref(words, 1, 0, "int32", 8)
    np.testing.assert_allclose(np.asarray(outs[3][0]), np.asarray(ref_s), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[3][1]), np.asarray(ref_c), rtol=1e-5)

    # ... and the solo kernels agree with the same fused outputs
    solo_pk, solo_m = filter_project(words, reqs[1].geom, pred_word=4,
                                     pred_op="gt", pred_k=10)
    np.testing.assert_array_equal(np.asarray(outs[1][0]), np.asarray(solo_pk))
    np.testing.assert_array_equal(np.asarray(outs[1][1]), np.asarray(solo_m))
    solo_agg = aggregate(words, agg_word=1, pred_word=3, pred_op="lt", pred_k=5)
    np.testing.assert_allclose(np.asarray(outs[2]), np.asarray(solo_agg), rtol=1e-6)
    solo_s, solo_c = groupby_sum(words, group_word=1, agg_word=0, num_groups=8)
    np.testing.assert_allclose(np.asarray(outs[3][0]), np.asarray(solo_s), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(outs[3][1]), np.asarray(solo_c), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(outs[0]), np.asarray(project_any(words, reqs[0].geom,
                                                    revision=revision))
    )


@pytest.mark.parametrize("revision", ["mlp", "xla"])
def test_scan_multi_fused_mvcc_snapshot(revision):
    """Deleted rows disappear from snapshot-enabled requests of the fused
    pass — and padded tail rows never contribute."""
    schema, t = make_table(n=333, row_bytes=32)
    ts0 = t.now()
    t.delete(np.arange(0, 333, 2))  # kill even rows after ts0
    words = jnp.asarray(t.words())
    ts_word = schema.row_words
    g = TableGeometry.from_schema(schema, ["A1", "A2"], t.row_count)
    reqs = (
        AggregateRequest(agg_word=0, ts_word=ts_word, ts=ts0),
        AggregateRequest(agg_word=0, ts_word=ts_word, ts=t.now()),
        FilterRequest(g, pred_word=1, pred_op="gt", pred_k=-1000,
                      ts_word=ts_word, ts=t.now()),
        GroupByRequest(group_word=1, agg_word=0, num_groups=4,
                       ts_word=ts_word, ts=t.now()),
    )
    outs = scan_multi(words, reqs, revision=revision, block_rows=64)
    assert int(outs[0][1]) == 333  # the old snapshot still sees every row
    assert int(outs[1][1]) == 333 // 2  # only the 166 odd rows live now
    valid = np.asarray(R.mvcc_mask_ref(words, ts_word, t.now()))
    ref_pk, ref_m = R.filter_project_ref(
        words, g, 1, "int32", "gt", -1000, valid=jnp.asarray(valid)
    )
    np.testing.assert_array_equal(np.asarray(outs[2][0]), np.asarray(ref_pk))
    np.testing.assert_array_equal(np.asarray(outs[2][1]), np.asarray(ref_m))
    ref_s, ref_c = R.groupby_sum_ref(words, 1, 0, "int32", 4,
                                     valid=jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(outs[3][0]), np.asarray(ref_s), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[3][1]), np.asarray(ref_c), rtol=1e-5)


def test_request_intervals_and_union_geometry():
    schema, _ = make_table(n=10)
    g = TableGeometry.from_schema(schema, ["A1", "A2"], 10)
    # an unpredicated aggregate enables only its aggregate word
    assert request_intervals(AggregateRequest(agg_word=5)) == [(20, 4)]
    # the predicate word and both MVCC timestamp words ride along when enabled
    spans = request_intervals(
        AggregateRequest(agg_word=5, pred_word=2, pred_op="gt", ts_word=16)
    )
    assert (20, 4) in spans and (8, 4) in spans and (64, 8) in spans
    # adjacent/overlapping intervals collapse into one burst chain
    u = union_geometry(
        (ProjectRequest(g), AggregateRequest(agg_word=2)), row_bytes=64,
        row_count=10,
    )
    assert u.col_widths == (12,) and u.abs_offsets == (0,)
    with pytest.raises(ValueError):
        union_geometry((), row_bytes=64, row_count=10)


def test_scan_multi_rejects_empty_and_narrow_storage():
    schema, t = make_table(n=8)
    words = jnp.asarray(t.words())
    with pytest.raises(ValueError):
        scan_multi(words, ())
    wide = TableGeometry.from_schema(benchmark_schema(128, 4), ["A32"], 8)
    with pytest.raises(ValueError):
        scan_multi(words[:, :4], (ProjectRequest(wide),))


# ------------------------------------------------------------ engine layer
@pytest.mark.parametrize("revision", REVISIONS)
def test_execute_many_mixed_matches_solo_paths(revision):
    schema, t = make_table(n=400)
    eng = RelationalMemoryEngine(revision=revision)
    ex = BatchExecutor(eng)
    v = ex.add_columns(t, ("A1", "A2", "A3", "A4"))
    ex.add_filter(t, ("A1", "A3"), "A5", "gt", 10)
    ex.add_aggregate(t, "A2", "A4", "lt", 5)
    ex.add_groupby(t, "A2", "A1", 8)
    assert len(ex) == 4
    outs = ex.submit()
    assert len(ex) == 0 and ex.submit() == []
    assert eng.stats.shared_scans == 1  # four ops, one pass
    assert eng.stats.uploads == 1

    solo = RelationalMemoryEngine(revision=revision)
    np.testing.assert_array_equal(
        np.asarray(outs[0]), np.asarray(solo.register(t, v.columns).packed())
    )
    words = solo.device_words(t)
    geom_f = TableGeometry.from_schema(schema, ["A1", "A3"], t.row_count)
    solo_pk, solo_m = filter_project(words, geom_f, pred_word=4,
                                     pred_op="gt", pred_k=10)
    np.testing.assert_array_equal(np.asarray(outs[1][0]), np.asarray(solo_pk))
    np.testing.assert_array_equal(np.asarray(outs[1][1]), np.asarray(solo_m))
    s, c = solo.aggregate(t, "A2", "A4", "lt", 5)
    assert (float(outs[2][0]), float(outs[2][1])) == (s, c)
    solo_s, solo_c = groupby_sum(words, group_word=1, agg_word=0, num_groups=8)
    np.testing.assert_allclose(np.asarray(outs[3][0]), np.asarray(solo_s), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(outs[3][1]), np.asarray(solo_c), rtol=1e-6)


def test_execute_many_dedupes_equal_requests_and_serves_hot():
    _, t = make_table(n=300)
    eng = RelationalMemoryEngine()
    warm = eng.register(t, ("A2", "A4"))
    _ = warm.packed()  # pre-warm one projection
    ops = [
        ProjectOp(eng.register(t, ("A2", "A4"))),  # hot
        AggregateOp(t, "A1"),
        AggregateOp(t, "A1"),  # identical: must share one output slot
        AggregateOp(t, "A1", "A3", "gt", 0),  # different predicate: its own
        GroupByOp(t, "A2", "A1", 8),
    ]
    hot_before = eng.stats.hot_hits
    outs = execute_batch(eng, ops)
    assert eng.stats.hot_hits == hot_before + 1
    assert eng.stats.shared_scans == 1  # 3 distinct cold requests, one pass
    assert eng.stats.cold_misses == 1 + 4  # warm-up + the four cold ops
    np.testing.assert_array_equal(np.asarray(outs[1]), np.asarray(outs[2]))
    assert float(outs[1][1]) == t.row_count
    assert float(outs[3][1]) < t.row_count  # the predicated twin differs
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(warm.packed()))


def test_fused_pass_charges_union_bytes_once():
    """The mixed pass charges the union geometry's bus beats — strictly fewer
    than the same ops executed one at a time on an identical engine."""
    _, t = make_table(n=1000)
    mk = lambda: [  # noqa: E731 — tiny op-batch factory
        ProjectOp(eng.register(t, ("A1", "A2"))),
        AggregateOp(t, "A2", "A4", "lt", 5),
        GroupByOp(t, "A3", "A1", 8),
    ]
    eng = RelationalMemoryEngine()
    batch_ops = mk()
    eng.execute_many(batch_ops)
    fused_bytes = eng.stats.bytes_from_dram
    assert fused_bytes == eng.scan_bytes(t, tuple(o.lower() for o in batch_ops))

    eng = RelationalMemoryEngine()
    for op in mk():
        eng.execute_many([op])
    assert eng.stats.shared_scans == 0  # solo ops keep the single-op kernels
    assert fused_bytes < eng.stats.bytes_from_dram


def test_vmem_budget_guard_halves_block_rows():
    schema, t = make_table(n=2000)
    reqs = tuple(
        ProjectRequest(TableGeometry.from_schema(schema, [f"A{i + 1}"], 2000))
        for i in range(8)
    )
    # the modeled footprint shrinks linearly with the tile height; the row
    # tile is the *storage* stride (hidden MVCC words ride in the stream)
    big = scan_vmem_footprint_bytes(reqs, t.row_words, 256)
    assert scan_vmem_footprint_bytes(reqs, t.row_words, 128) == big // 2

    tight = RelationalMemoryEngine(vmem_bytes=big // 4)
    ops = [ProjectOp(tight.register(t, [f"A{i + 1}"])) for i in range(8)]
    outs = tight.execute_many(ops)
    assert tight.stats.last_block_rows == 64  # halved 256 -> 128 -> 64
    solo = RelationalMemoryEngine()
    for i, out in enumerate(outs):  # tile choice never changes results
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(solo.register(t, [f"A{i + 1}"]).packed())
        )

    floor = RelationalMemoryEngine(vmem_bytes=1)  # absurd budget: floor holds
    floor.execute_many([ProjectOp(floor.register(t, [f"A{i + 1}"]))
                        for i in range(8)])
    assert floor.stats.last_block_rows == 32

    roomy = RelationalMemoryEngine()  # 2 MB default: no shrink needed here
    roomy.execute_many([ProjectOp(roomy.register(t, [f"A{i + 1}"]))
                        for i in range(2)])
    assert roomy.stats.last_block_rows == roomy.block_rows


def test_executor_snapshot_ops_respect_mvcc():
    """Snapshot-carrying filter/aggregate ops fused into one pass see only
    the rows live at their snapshot time."""
    _, t = make_table(n=200, row_bytes=32)
    ts0 = t.now()
    keep = np.asarray(t.read_column("A1")[1::2], dtype=np.float64)
    t.delete(np.arange(0, 200, 2))
    eng = RelationalMemoryEngine()
    ex = BatchExecutor(eng)
    ex.add_aggregate(t, "A1", snapshot_ts=ts0)
    ex.add_aggregate(t, "A1", snapshot_ts=t.now())
    ex.add_filter(t, ("A1", "A2"), "A2", "gt", -1000, snapshot_ts=t.now())
    before, after, (packed, mask) = ex.submit()
    assert eng.stats.shared_scans == 1
    assert int(before[1]) == 200
    assert int(after[1]) == 100
    np.testing.assert_allclose(float(after[0]), keep.sum(), rtol=1e-6)
    assert int(np.asarray(mask).sum()) == 100  # dead rows fail validity
    assert not np.asarray(packed)[::2].any()  # ...and are zeroed in the block


def test_executor_rejects_foreign_filter_views():
    _, t = make_table(n=50)
    eng1, eng2 = RelationalMemoryEngine(), RelationalMemoryEngine()
    ex = BatchExecutor(eng1)
    with pytest.raises(ValueError):
        ex.add_op(FilterOp(eng2.register(t, ("A1",)), "A2"))


# ------------------------------------------------------------ server layer
def test_mixed_kind_tick_is_one_shared_scan():
    """The acceptance check: a mixed-kind same-table tick performs exactly
    one shared scan, and every result matches its solo execution."""
    _, t = make_table(n=400)
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    t_proj = server.submit(plan(t).project("A1", "A3"))
    t_filt = server.submit(plan(t).filter("A5", "gt", 10).project("A1", "A2"))
    t_agg = server.submit(plan(t).filter("A4", "lt", 5).sum("A2"))
    t_gb = server.submit(plan(t).groupby("A2", "A1", "avg", 16))
    server.run_tick()
    assert eng.stats.shared_scans == 1  # one pass answered all four kinds
    assert eng.stats.uploads == 1
    assert t_proj.route == "rme"
    assert t_filt.route == "fused-filter"
    assert t_agg.route == "fused-aggregate"
    assert t_gb.route == "fused-groupby"
    assert server.stats.table_groups == 1
    assert server.stats.shared_scan_ratio == 1.0
    assert server.stats.bytes_saved > 0

    solo = RelationalMemoryEngine()
    np.testing.assert_array_equal(
        np.asarray(t_proj.result(timeout=5)),
        np.asarray(solo.register(t, ("A1", "A3")).packed()),
    )
    geom = TableGeometry.from_schema(t.schema, ["A1", "A2"], t.row_count)
    ref_pk, ref_m = filter_project(solo.device_words(t), geom, pred_word=4,
                                   pred_op="gt", pred_k=10)
    got_pk, got_m = t_filt.result(timeout=5)
    np.testing.assert_array_equal(np.asarray(got_pk), np.asarray(ref_pk))
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(ref_m))
    s, _ = solo.aggregate(t, "A2", "A4", "lt", 5)
    assert t_agg.result(timeout=5) == s
    ref_s, ref_c = groupby_sum(solo.device_words(t), group_word=1, agg_word=0,
                               num_groups=16)
    np.testing.assert_allclose(
        np.asarray(t_gb.result(timeout=5)),
        np.asarray(ref_s) / np.maximum(np.asarray(ref_c), 1.0), rtol=1e-6,
    )


def test_bad_query_does_not_poison_the_tick():
    """One client's unservable query (int64 aggregate: fused kernels decode
    4-byte words only) fails its own ticket — the other clients' results
    still arrive.  Compile-time dtype validation catches the known case, and
    the per-query fallback guards the shared step against anything else."""
    from repro.core import paper_schema

    rng = np.random.default_rng(5)
    schema = paper_schema()
    n = 128
    cols = {}
    for c in schema.columns:
        if c.dtype == "char":
            cols[c.name] = (rng.integers(0, 256, (n, c.width)).astype(np.uint8)
                            .view(np.dtype((np.bytes_, c.width))).reshape(-1))
        elif c.dtype == "int64":
            cols[c.name] = np.arange(n, dtype=np.int64)
        else:
            cols[c.name] = rng.integers(-50, 50, n).astype(np.int32)
    t = RelationalTable.from_columns(schema, cols)
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    good = server.submit(plan(t).project("num_fld1"))
    bad = server.submit(plan(t).sum("key"))  # int64: inexpressible fused
    server.run_tick()
    with pytest.raises(ValueError, match="4-byte numeric"):
        bad.result(timeout=5)
    np.testing.assert_array_equal(
        np.asarray(good.result(timeout=5))[:, 0],
        np.asarray(t.read_column("num_fld1")),
    )
    assert server.stats.served == 1 and server.stats.failed == 1


def test_shared_step_fallback_isolates_the_offender():
    """If the shared pass itself dies mid-tick, healthy queries are re-run
    individually instead of inheriting the batch's error."""
    _, t = make_table(n=100)
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    real = eng.execute_many
    calls = {"n": 0}

    def flaky(ops):
        calls["n"] += 1
        if calls["n"] == 1 and len(ops) > 1:  # only the coalesced launch dies
            raise RuntimeError("fused pass failed to lower")
        return real(ops)

    eng.execute_many = flaky
    tk1 = server.submit(plan(t).project("A1", "A2"))
    tk2 = server.submit(plan(t).filter("A4", "lt", 5).sum("A2"))
    server.run_tick()
    solo = RelationalMemoryEngine()
    np.testing.assert_array_equal(
        np.asarray(tk1.result(timeout=5)),
        np.asarray(solo.register(t, ("A1", "A2")).packed()),
    )
    s, _ = solo.aggregate(t, "A2", "A4", "lt", 5)
    assert tk2.result(timeout=5) == s
    assert server.stats.served == 2 and server.stats.failed == 0


def test_shared_step_fallback_is_counted():
    """A shared pass that raised and was re-run query by query shows up in
    ``shared_pass_fallbacks`` (and in ``snapshot()``)."""
    _, t = make_table(n=100)
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    real = eng.execute_many

    def flaky(ops):
        if len(ops) > 1:
            raise RuntimeError("fused pass failed to lower")
        return real(ops)

    eng.execute_many = flaky
    server.submit(plan(t).project("A1", "A2"))
    server.submit(plan(t).sum("A2"))
    server.run_tick()
    assert server.stats.served == 2
    assert server.stats.shared_pass_fallbacks == 1
    assert server.snapshot()["shared_pass_fallbacks"] == 1
    assert server.snapshot()["engine_kernel_fallbacks"] == 0


def test_mixed_kinds_two_tables_two_scans():
    _, t1 = make_table(n=300, seed=1)
    _, t2 = make_table(n=200, seed=2)
    eng = RelationalMemoryEngine()
    server = QueryServer(eng)
    for t in (t1, t2):
        server.submit(plan(t).project("A1", "A2"))
        server.submit(plan(t).filter("A4", "lt", 5).sum("A2"))
    server.run_tick()
    assert eng.stats.shared_scans == 2  # one fused pass per table
    assert server.stats.table_groups == 2
    assert server.stats.shared_scan_ratio == 1.0
