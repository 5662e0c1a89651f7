"""Device-resident join offload: route equality, MVCC snapshots, coalescing.

The contract under test: the ``device-hash-join`` route (cached build-side
hash partitions + Pallas/XLA probe over the device row store) produces
bit-identical :class:`~repro.core.requests.JoinResult` outputs to the host
sort-probe route and the pure-jnp oracle, across every engine revision; a
snapshot-pinned join is byte-identical to joining frozen copies of both
tables; a mixed-kind server tick containing a join still performs exactly
one shared probe-side scan; and a Pallas lowering failure falls back to the
XLA probe without changing results.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    RelationalMemoryEngine,
    RelationalTable,
    benchmark_schema,
    compile_plan,
    decompose,
    plan,
)
from repro.core import operators as ops
from repro.core import planner
from repro.core.requests import AggregateOp
from repro.kernels import common, ref
from repro.kernels import rme_join as KJ
from repro.serve import QueryServer

REVISIONS = ("bsl", "pck", "mlp", "xla")
N_S, N_R = 500, 96


def _join_plan(t, rt):
    return plan(t).join(rt, key="A2", left_proj="A1", right_proj="A3")


@pytest.fixture
def table():
    rng = np.random.default_rng(3)
    schema = benchmark_schema(64, 4)
    cols = {c.name: rng.integers(-100, 100, N_S).astype(np.int32)
            for c in schema.columns}
    cols["A2"] = rng.integers(-20, 2 * N_R, N_S).astype(np.int32)
    return RelationalTable.from_columns(schema, cols)


@pytest.fixture
def build_table(table):
    rng = np.random.default_rng(7)
    cols = {c.name: rng.integers(-50, 50, N_R).astype(np.int32)
            for c in table.schema.columns}
    cols["A2"] = np.arange(N_R, dtype=np.int32)  # primary key
    return RelationalTable.from_columns(table.schema, cols)


def _assert_join_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.matched), np.asarray(b.matched))
    np.testing.assert_array_equal(np.asarray(a.r_proj), np.asarray(b.r_proj))
    np.testing.assert_array_equal(np.asarray(a.s_proj), np.asarray(b.s_proj))


# ------------------------------------------------------- route equality
@pytest.mark.parametrize("revision", REVISIONS)
def test_device_equals_host_equals_ref(table, build_table, revision):
    """device-hash-join == host sort-probe == kernels/ref.py, bit-exact."""
    eng = RelationalMemoryEngine(revision=revision)
    ops.clear_join_build_cache()
    q = _join_plan(table, build_table)
    pq = compile_plan(eng, q)
    assert pq.route == "device-hash-join"
    device = pq.run()
    host = compile_plan(eng, q, join_route="shared-scan-join").run()
    oracle_s, oracle_r, oracle_m = ref.hash_join_ref(
        jnp.asarray(table.read_column("A2")),
        jnp.asarray(table.read_column("A1")),
        jnp.asarray(build_table.read_column("A2")),
        jnp.asarray(build_table.read_column("A3")),
    )
    _assert_join_equal(device, host)
    np.testing.assert_array_equal(np.asarray(device.matched), np.asarray(oracle_m))
    np.testing.assert_array_equal(np.asarray(device.r_proj), np.asarray(oracle_r))
    np.testing.assert_array_equal(np.asarray(device.s_proj), np.asarray(oracle_s))
    assert np.asarray(device.matched).any()  # the fixture joins non-trivially


def test_stride_aligned_keys_spread_and_stay_exact(table):
    """Stride-aligned keys — the pattern that collapses a modulo hash into
    one bucket and blows the dense (P, C) arrays up to P x n words — must
    spread under the Fibonacci mix (bounded capacity) and join exactly."""
    rng = np.random.default_rng(1)
    n_r = 512
    cols = {c.name: rng.integers(-9, 9, n_r).astype(np.int32)
            for c in table.schema.columns}
    # every key ≡ 1 (mod any power-of-two bucket count ≤ 1024): one bucket
    # under `key mod P`, uniform under the multiplicative hash
    cols["A2"] = (np.arange(n_r, dtype=np.int32) * 1024) + 1
    rt = RelationalTable.from_columns(table.schema, cols)
    parts = KJ.build_partitions(cols["A2"], cols["A3"])
    assert parts.capacity <= 4 * KJ.TARGET_BUCKET_LOAD  # no blowup
    assert parts.nbytes <= 8 * KJ.estimated_partition_bytes(n_r)
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    q = _join_plan(table, rt)
    device = compile_plan(eng, q).run()
    host = compile_plan(eng, q, join_route="shared-scan-join").run()
    _assert_join_equal(device, host)


def test_partition_invariants():
    """Kernel-level honesty: capacity is the max occupancy, no key is lost,
    and empty-slot fills can never hash to the bucket that holds them."""
    rng = np.random.default_rng(5)
    key = rng.choice(np.arange(-500, 500, dtype=np.int32), 200, replace=False)
    parts = KJ.build_partitions(key, np.ones(200, np.int32))
    p, c = parts.num_buckets, parts.capacity
    g = KJ.bucket_of_np(key, p)
    assert c == np.bincount(g, minlength=p).max()
    keys = np.asarray(parts.keys)
    fills = KJ.bucket_fills(p)
    for b in range(p):
        in_bucket = np.sort(key[g == b])
        slots = keys[b]
        real = slots[KJ.bucket_of_np(slots, p) == b]
        assert np.array_equal(np.sort(real), in_bucket)  # nothing lost
        pad = slots[KJ.bucket_of_np(slots, p) != b]
        assert (pad == fills[b]).all()  # fill never hashes to its own bucket
    # the fill-safety theorem itself, for every bucket count the builder uses
    for pb in (2, 8, 64, 1024):
        f = KJ.bucket_fills(pb)
        assert (KJ.bucket_of_np(f, pb) != np.arange(pb)).all()


I32 = np.iinfo(np.int32)
# words whose bytes cover every edge: sign bit, all ones, zero, alternating
# bytes, and values above 2^24 that a float32 cannot hold
EDGE_WORDS = np.array([I32.min, I32.max, -1, 0, 1, 0x00FF00FF, -0x00FF0100,
                       (1 << 24) + 1, -(1 << 24) - 3, 0x7F00FF01], np.int32)
EXACT_CASES = [("edges", False), ("edges", True), ("fills", False),
               ("fills", True), ("wide", False), ("wide", True),
               ("versions", True)]


def _exact_build_side(case, rng):
    """``(key, val, begin, end)`` of a build side for one exactness case;
    keys are unique except in ``versions``, MVCC version triples of which at
    most one is visible at ts = 10."""
    rand = rng.integers(I32.min, I32.max, 400, dtype=np.int64).astype(np.int32)
    if case == "edges":
        key = np.unique(np.concatenate([EDGE_WORDS, rand[:200]]))
    elif case == "fills":  # the fill words 0 and 1 are never build keys
        key = np.setdiff1d(rand[:200], [0, 1])
    elif case == "wide":  # 48 distinct keys crafted to collide in one bucket
        p = KJ.num_buckets_for(200)
        pool = rng.integers(I32.min, I32.max, 1 << 16, dtype=np.int64)
        crowd = np.unique(pool[KJ.bucket_of_np(pool, p) == 3].astype(np.int32))
        rest = np.setdiff1d(rand, crowd)
        rest = rest[KJ.bucket_of_np(rest, p) != 3][:152]
        key = np.concatenate([crowd[:48], rest])
    else:  # versions
        key = np.repeat(np.unique(np.concatenate([EDGE_WORDS, rand[:90]])), 3)
    n = key.shape[0]
    val = np.resize(np.concatenate([EDGE_WORDS[::-1], rand[200:]]), n)
    if case == "versions":
        # each key's versions live in [0, 8), [8, 12), [12, 20): only the
        # middle one is visible at ts = 10, unless a random end cuts it
        begin = np.tile(np.array([0, 8, 12], np.int32), n // 3)
        end = np.tile(np.array([8, 12, 20], np.int32), n // 3)
        end[1::3] = np.where(rng.random(n // 3) < 0.2, 9, 12)
    else:
        begin = rng.integers(0, 20, n).astype(np.int32)
        end = begin + rng.integers(0, 20, n).astype(np.int32)
    return key.astype(np.int32), val.astype(np.int32), begin, end


@pytest.mark.parametrize("case, build_ts", EXACT_CASES,
                         ids=[f"{c}-{'ts' if t else 'nots'}"
                              for c, t in EXACT_CASES])
def test_byte_plane_probe_is_bit_exact(case, build_ts):
    """The Pallas probe selects bucket words through bfloat16 byte planes:
    every int32 key, payload and timestamp word must come back bit-exact —
    edge words, words above 2^24, the bucket fills, and a crowded bucket
    whose 8·C planes span more than one 128-lane tile."""
    rng = np.random.default_rng(sum(map(ord, case)) + build_ts)
    key, val, begin, end = _exact_build_side(case, rng)
    parts = KJ.build_partitions(key, val, begin, end)
    p, c = parts.num_buckets, parts.capacity
    k = KJ.plane_lanes(c)
    assert parts.kv_planes.shape == parts.ts_planes.shape == (p, k)
    assert parts.kv_planes.dtype == jnp.bfloat16
    assert parts.nbytes == 4 * p * c * 4 + 2 * p * k * 2  # planes counted
    assert parts.nbytes <= 8 * KJ.estimated_partition_bytes(key.shape[0])
    if case == "wide":
        assert c > 16 and k > 128
    ts = 10
    visible = (begin <= ts) & (ts < end) if build_ts else np.ones_like(key, bool)
    n_s = 700  # not a whole number of row tiles
    s_key = np.concatenate([
        rng.permutation(key)[:300], EDGE_WORDS, np.zeros(20, np.int32),
        np.ones(20, np.int32),
        rng.integers(I32.min, I32.max, n_s, dtype=np.int64).astype(np.int32),
    ])[:n_s]
    s_key = rng.permutation(s_key)
    s_val = rng.integers(I32.min, I32.max, n_s, dtype=np.int64).astype(np.int32)
    words = jnp.asarray(np.stack([s_val, s_key], axis=1))
    want = ref.hash_join_ref(jnp.asarray(s_key), jnp.asarray(s_val),
                             jnp.asarray(key[visible]),
                             jnp.asarray(val[visible]))
    assert np.asarray(want[2]).any()
    for got in (KJ.hash_join(words, parts, 1, 0, ts=ts, build_ts=build_ts,
                             interpret=True),
                KJ.hash_join_xla(words, parts, 1, 0, ts=ts,
                                 build_ts=build_ts)):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------- MVCC snapshots
def test_snapshot_join_byte_identical_to_frozen_copy(table, build_table):
    """A snapshot-pinned join under concurrent writes on BOTH sides equals
    the plain join of copies frozen at the snapshot."""
    frozen_s = RelationalTable.from_columns(
        table.schema,
        {c.name: table.read_column(c.name) for c in table.schema.columns},
    )
    frozen_r = RelationalTable.from_columns(
        build_table.schema,
        {c.name: build_table.read_column(c.name)
         for c in build_table.schema.columns},
    )
    ts0 = max(table.now(), build_table.now())
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    q = _join_plan(table, build_table)
    pinned = compile_plan(eng, q, snapshot_ts=ts0)
    assert pinned.route == "device-hash-join"

    # concurrent writes: delete + update probe rows, delete build rows,
    # append rows on both sides
    table.delete(np.arange(25))
    table.update(np.arange(30, 40),
                 {"A1": np.full(10, 7777, np.int32)})
    build_table.delete(np.arange(10, 30))
    table.append({c.name: np.full(8, 3, np.int32)
                  for c in table.schema.columns})
    build_table.append({c.name: np.full(4, 2, np.int32)
                        for c in build_table.schema.columns})

    got = pinned.run()
    want = compile_plan(RelationalMemoryEngine(),
                        _join_plan(frozen_s, frozen_r)).run()
    n0 = frozen_s.row_count
    got_m = np.asarray(got.matched)
    np.testing.assert_array_equal(got_m[:n0], np.asarray(want.matched))
    np.testing.assert_array_equal(np.asarray(got.r_proj)[:n0],
                                  np.asarray(want.r_proj))
    np.testing.assert_array_equal(np.asarray(got.s_proj)[:n0],
                                  np.asarray(want.s_proj))
    # physical rows born after the snapshot are invisible: zeros, unmatched
    assert not got_m[n0:].any()
    assert np.asarray(got.s_proj)[n0:].sum() == 0
    assert np.asarray(got.r_proj)[n0:].sum() == 0


def test_snapshot_join_through_query_server(table, build_table):
    """Acceptance: a join submitted with a snapshot through the QueryServer
    no longer raises PlanError — it serves from the post-write tick snapshot."""
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    plain = compile_plan(eng, _join_plan(table, build_table)).run()

    server = QueryServer(eng)  # auto snapshot mode: pins on first write
    server.submit_delete(table, np.arange(15))
    tk = server.submit(_join_plan(table, build_table))
    server.run_tick()
    res = tk.result(timeout=30)
    assert tk.route == "device-hash-join"
    m = np.asarray(res.matched)
    assert not m[:15].any()  # tick-deleted probe rows are invisible
    np.testing.assert_array_equal(m[15:], np.asarray(plain.matched)[15:])

    # forced-snapshot mode serves a build-side write the same way
    server2 = QueryServer(eng, snapshot_reads=True)
    server2.submit_delete(build_table, np.arange(5))
    tk2 = server2.submit(_join_plan(table, build_table))
    server2.run_tick()
    res2 = tk2.result(timeout=30)
    # one slot per *physical* probe row: read keys from the raw row store
    keys = table.words()[:, table.schema.word_offset("A2")]
    dead = np.isin(keys, np.arange(5))
    assert not (np.asarray(res2.matched) & dead).any()


# ------------------------------------------------- tick coalescing
def test_mixed_tick_with_join_is_one_shared_scan(table, build_table):
    """A tick mixing a join with co-tick filters/aggregates/group-bys on the
    probe table performs exactly ONE shared probe-side scan (the join's
    probe-side projection rides the same fused pass)."""
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    server = QueryServer(eng)
    tks = [
        server.submit(_join_plan(table, build_table)),
        server.submit(plan(table).filter("A3", "gt", 0).sum("A1")),
        server.submit(plan(table).groupby("A4", "A1", "avg", 8)),
        server.submit(plan(table).filter("A5", "lt", 0).project("A2")),
    ]
    server.run_tick()
    results = [tk.result(timeout=30) for tk in tks]
    assert eng.stats.shared_scans == 1  # one pass served every kind + join
    ref_join = compile_plan(RelationalMemoryEngine(),
                            _join_plan(table, build_table)).run()
    _assert_join_equal(results[0], ref_join)
    a1, a3 = table.read_column("A1"), table.read_column("A3")
    assert results[1] == pytest.approx(float(a1[a3 > 0].sum()))


def test_join_dedupes_with_same_view_projection(table, build_table):
    """A co-tick projection of exactly the join's probe view shares one
    output slot in the fused pass — and the packed block still crosses to
    the CPU only for the projection consumer."""
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    server = QueryServer(eng)
    tk_join = server.submit(_join_plan(table, build_table))
    tk_proj = server.submit(plan(table).project("A1", "A2"))
    server.run_tick()
    res_join, res_proj = tk_join.result(timeout=30), tk_proj.result(timeout=30)
    assert eng.stats.shared_scans == 0  # dedupe left one request: solo kernel
    expect = eng.register(table, ("A1", "A2")).packed()
    np.testing.assert_array_equal(np.asarray(res_proj), np.asarray(expect))
    assert np.asarray(res_join.matched).any()


def test_solo_device_join_moves_fewer_bytes_than_host(table, build_table):
    """The fig12 criterion at test scale: on one engine, the device route's
    row-store + hierarchy bytes are strictly below the host sort-probe's."""
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    q = _join_plan(table, build_table)

    eng.stats.reset()
    compile_plan(eng, q, join_route="device-hash-join").run()
    device = (eng.stats.bytes_from_dram + eng.stats.bytes_to_cpu
              + eng.stats.bytes_uploaded)

    eng.cache.reset()
    ops.clear_join_build_cache()
    eng.rowstore.clear()
    eng.stats.reset()
    compile_plan(eng, q, join_route="shared-scan-join").run()
    host = (eng.stats.bytes_from_dram + eng.stats.bytes_to_cpu
            + eng.stats.bytes_uploaded)
    assert device < host


def test_route_chooser_prefers_host_when_everything_is_warm(
    table, build_table
):
    """Cost model sanity: with the probe view hot in the reorg cache and the
    sorted index cached, the host sort-probe costs ~0 bytes and wins."""
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    q = _join_plan(table, build_table)
    compile_plan(eng, q, join_route="shared-scan-join").run()  # warm both
    assert compile_plan(eng, q).route == "shared-scan-join"


def test_partition_cache_invalidates_on_build_mutation(table, build_table):
    """A build-side write changes the version key: the next compile misses,
    rebuilds, and the dead version's buckets are dropped rather than
    accumulating.  A snapshot pinned *before* the write keeps resolving the
    pre-write payload out of the freshly built buckets (MVCC on the build
    side)."""
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    q = _join_plan(table, build_table)
    first = compile_plan(eng, q).run()
    assert ops.JOIN_BUILD_STATS == {"hits": 0, "misses": 1}
    ts0 = max(table.now(), build_table.now())
    build_table.update(np.array([0]), {"A3": np.array([999], np.int32)})
    pinned = compile_plan(eng, q, snapshot_ts=ts0).run()
    assert ops.JOIN_BUILD_STATS["misses"] == 2
    keys = [k for k in ops._BUILD_INDEX_CACHE if k[0] == build_table.uid]
    assert len(keys) == 1  # the dead version's buckets were dropped
    # pinned before the update: byte-identical to the pre-write join
    _assert_join_equal(pinned, first)


def test_probe_streams_multiple_resident_chunks(table, build_table):
    """A probe table grown after residency keeps base + tail chunks; the
    solo probe streams each chunk and concatenates — equal to the
    single-buffer answer."""
    eng = RelationalMemoryEngine()
    ops.clear_join_build_cache()
    eng.device_words(table)  # resident at the pre-append watermark
    n_new = 40
    table.append({c.name: np.arange(n_new, dtype=np.int32)
                  for c in table.schema.columns})
    assert len(eng.device_chunks(table)) == 2  # base + appended tail
    got = compile_plan(eng, _join_plan(table, build_table)).run()
    want = compile_plan(RelationalMemoryEngine(),
                        _join_plan(table, build_table)).run()
    _assert_join_equal(got, want)
    assert np.asarray(got.matched).shape[0] == table.row_count


# ------------------------------------------------- lowering fallback
def test_fallback_when_device_lowering_fails(table, build_table, monkeypatch):
    """A Pallas probe failure falls back to the XLA fused-gather probe with
    identical results — one query's lowering error never loses the join."""
    calls = {"n": 0}

    def boom(*args, **kwargs):
        calls["n"] += 1
        raise RuntimeError("synthetic lowering failure")

    import repro.kernels.ops as kernel_ops

    monkeypatch.setattr(kernel_ops, "hash_join", boom)
    eng = RelationalMemoryEngine(revision="mlp")
    ops.clear_join_build_cache()
    got = compile_plan(eng, _join_plan(table, build_table)).run()
    assert calls["n"] == 1  # the Pallas probe was attempted and failed
    want = compile_plan(RelationalMemoryEngine(),
                        _join_plan(table, build_table)).run()
    _assert_join_equal(got, want)


def test_lowering_failure_counts_kernel_fallbacks(table, build_table,
                                                  monkeypatch):
    """Every serve the XLA probe takes instead of the Pallas kernel is
    counted — the counter a chip run checks to prove it timed the kernel."""
    import repro.kernels.ops as kernel_ops

    def boom(*args, **kwargs):
        raise RuntimeError("synthetic lowering failure")

    eng = RelationalMemoryEngine(revision="mlp")
    ops.clear_join_build_cache()
    compile_plan(eng, _join_plan(table, build_table)).run()
    assert eng.stats.kernel_fallbacks == 0  # healthy probe: the kernel ran
    monkeypatch.setattr(kernel_ops, "hash_join", boom)
    got = compile_plan(eng, _join_plan(table, build_table)).run()
    assert eng.stats.kernel_fallbacks == 1
    want = compile_plan(RelationalMemoryEngine(),
                        _join_plan(table, build_table)).run()
    _assert_join_equal(got, want)


def test_scan_lowering_failure_counts_kernel_fallbacks(table, monkeypatch):
    """The scan twin: a failed fused pass and a failed solo kernel each
    count one fallback serve, and the xla revision never counts."""
    import repro.kernels.ops as kernel_ops
    from repro.kernels import rme_scan_multi

    def boom(*args, **kwargs):
        raise RuntimeError("synthetic lowering failure")

    monkeypatch.setattr(rme_scan_multi, "scan_multi", boom)
    monkeypatch.setattr(kernel_ops, "aggregate", boom)
    eng = RelationalMemoryEngine(revision="mlp")
    fused = [AggregateOp(table, "A1"), AggregateOp(table, "A3")]
    got = eng.execute_many(fused)  # two requests: one fused pass
    assert eng.stats.kernel_fallbacks == 1
    eng.execute_many([AggregateOp(table, "A1")])  # solo kernel
    assert eng.stats.kernel_fallbacks == 2
    xla = RelationalMemoryEngine(revision="xla")
    want = xla.execute_many(fused)
    assert xla.stats.kernel_fallbacks == 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_probe_too_large_for_vmem_takes_counted_xla_route(
        table, build_table, monkeypatch):
    """A build side whose bucket arrays fit no row tile in the chip's VMEM
    is decided before dispatch: the XLA probe serves it, counted, and the
    kernel is never attempted."""
    import repro.kernels.ops as kernel_ops

    attempts = {"n": 0}
    real = kernel_ops.hash_join

    def counting(*args, **kwargs):
        attempts["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(kernel_ops, "hash_join", counting)
    eng = RelationalMemoryEngine(revision="mlp")
    ops.clear_join_build_cache()
    compile_plan(eng, _join_plan(table, build_table)).run()  # warm the build
    attempts["n"] = 0
    # as compiled for a chip whose VMEM holds no tile of these buckets
    monkeypatch.setattr(eng, "interpret", False)
    monkeypatch.setattr(eng, "_vmem_budget", lambda words: 1)
    got = compile_plan(eng, _join_plan(table, build_table)).run()
    assert attempts["n"] == 0 and eng.stats.kernel_fallbacks == 1
    want = compile_plan(RelationalMemoryEngine(),
                        _join_plan(table, build_table)).run()
    _assert_join_equal(got, want)


def test_probe_vmem_guard_on_v5e():
    """On a v5e's budget the bench-scale build side (P = 4096) probes at the
    full row tile, pinned or not; a 1M-row build side (P = 65536) probes at
    a quarter tile unpinned and fits no tile under a snapshot, whose second
    plane array it cannot hold."""
    limit = common.vmem_limit_bytes("TPU v5 lite")
    eng = RelationalMemoryEngine()

    def parts(p):
        z = jnp.zeros((p, 19), jnp.int32)
        planes = jnp.zeros((p, KJ.plane_lanes(19)), jnp.bfloat16)
        return KJ.JoinPartitions(z, z, z, z, planes, planes)

    for build_ts in (False, True):
        assert eng._probe_block_rows(parts(4096), 18, limit,
                                     build_ts) == eng.block_rows
    assert eng._probe_block_rows(parts(65536), 18, limit) == eng.block_rows // 4
    assert eng._probe_block_rows(parts(65536), 18, limit, True) is None
    with pytest.raises(ValueError, match="unknown TPU device kind"):
        common.vmem_limit_bytes("TPU v99")


def test_interpret_probe_sizes_tile_against_vmem_bytes(table, build_table):
    """In interpret mode the probe's row tile is sized against the engine's
    ``vmem_bytes``, the same budget as the fused pass: a tight budget halves
    the tile, an absurd one keeps the floor tile — never a fallback, and
    never a different result."""
    ops.clear_join_build_cache()
    roomy = RelationalMemoryEngine(revision="mlp")
    want = compile_plan(roomy, _join_plan(table, build_table)).run()
    assert roomy.stats.last_block_rows == roomy.block_rows
    parts = roomy._build_join_partitions(build_table, "A2", "A3")
    # row tiles of 2 or 18 words both pad to 128 lanes: one footprint
    full = KJ.probe_vmem_footprint_bytes(parts, table.row_words,
                                         roomy.block_rows)
    for budget, tile in ((full - 1, roomy.block_rows // 2), (1, 32)):
        eng = RelationalMemoryEngine(revision="mlp", vmem_bytes=budget)
        got = compile_plan(eng, _join_plan(table, build_table)).run()
        assert eng.stats.last_block_rows == tile
        assert eng.stats.kernel_fallbacks == 0
        _assert_join_equal(got, want)


def test_vmem_budget_from_chip_holding_row_store(table):
    """Compiled, both guards size against the VMEM of the chip that holds the
    row store, and row ranges against its HBM, asked of the device once per
    engine; an unknown chip raises rather than guess, and interpret mode
    keeps ``vmem_bytes`` and never cuts row ranges."""

    class Chip:
        def __init__(self, kind):
            self.device_kind = kind
            self.asked = 0

        def memory_stats(self):
            return {"bytes_limit": 16_909_336_064}

    class Words:
        def __init__(self, chip):
            self.chip = chip

        def devices(self):
            self.chip.asked += 1
            return {self.chip}

    eng = RelationalMemoryEngine(vmem_bytes=12345)
    v5e = Chip("TPU v5 lite")
    assert eng._vmem_budget(Words(v5e)) == 12345
    assert eng._kernel_row_limit(Words(v5e), (18,), 256) is None
    assert v5e.asked == 0
    eng.interpret = False
    want = common.vmem_limit_bytes("TPU v5 lite")
    assert eng._vmem_budget(Words(v5e)) == want
    assert eng._kernel_row_limit(Words(v5e), (18, 2), 256) == 1 << 21
    assert eng._vmem_budget(Words(v5e)) == want and v5e.asked == 1
    unknown = RelationalMemoryEngine()
    unknown.interpret = False
    with pytest.raises(ValueError, match="unknown TPU device kind"):
        unknown._vmem_budget(Words(Chip("cpu")))


def test_kernel_calls_split_into_row_ranges(table, build_table, monkeypatch):
    """A table larger than one compiled kernel call may take is served in
    row ranges — the fused pass, a solo kernel and both join probes (on the
    fused pass's packed block, and streamed over the row store) — with the
    results of one call."""
    import repro.kernels.ops as kernel_ops
    from repro.core import CompileOptions
    from repro.kernels import rme_scan_multi

    def run(eng):
        server = QueryServer(eng)
        tickets = [
            server.submit(plan(table).project("A1", "A2")),
            server.submit(plan(table).filter("A3", "gt", 10).project("A1")),
            server.submit(plan(table).filter("A4", "lt", 0).sum("A1")),
            server.submit(plan(table).groupby("A5", "A1", "sum", 8)),
            server.submit(_join_plan(table, build_table)),
        ]
        server.drain()
        solo_sum = compile_plan(
            plan(table).filter("A4", "lt", 0).sum("A1"), eng).run()
        solo_join = compile_plan(
            _join_plan(table, build_table), eng,
            options=CompileOptions(join_route="device-hash-join")).run()
        return [t.result() for t in tickets] + [solo_sum, solo_join]

    ops.clear_join_build_cache()
    want = run(RelationalMemoryEngine(revision="mlp"))
    calls = {"scan": 0, "solo": 0, "join": 0}

    def counted(name, fn):
        def wrapper(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(rme_scan_multi, "scan_multi",
                        counted("scan", rme_scan_multi.scan_multi))
    monkeypatch.setattr(kernel_ops, "aggregate",
                        counted("solo", kernel_ops.aggregate))
    monkeypatch.setattr(kernel_ops, "hash_join",
                        counted("join", kernel_ops.hash_join))
    monkeypatch.setattr(RelationalMemoryEngine, "_kernel_row_limit",
                        lambda self, words, widths, block_rows: 128)
    ops.clear_join_build_cache()
    eng = RelationalMemoryEngine(revision="mlp")
    got = run(eng)
    pieces = -(-N_S // 128)
    assert calls == {"scan": pieces, "solo": pieces, "join": 2 * pieces}
    assert eng.stats.kernel_fallbacks == 0
    for g, w in zip(got[:4] + got[5:6], want[:4] + want[5:6]):
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _assert_join_equal(got[4], want[4])
    _assert_join_equal(got[6], want[6])


def test_kernel_row_limit_on_v5e():
    """The smoke's mixed pass (row store, a projection, a filter and its
    mask) and its join probe take 2^20 rows per call on a v5e; the limit is
    a power of two and never below a row tile."""
    hbm = 16_909_336_064  # the bytes_limit a v5e reports
    assert common.kernel_row_limit(hbm, (18, 2, 2, 1), 256) == 1 << 20
    assert common.kernel_row_limit(hbm, (2, 1, 1, 1), 256) == 1 << 20
    assert common.kernel_row_limit(hbm, (18, 2), 256) == 1 << 21
    assert common.kernel_row_limit(hbm, (300,), 256) == 1 << 21
    assert common.kernel_row_limit(1 << 16, (18,), 256) == 256


def test_inexpressible_join_routes_to_host(table):
    """A char key cannot ride the device probe (integer-modulo hash): the
    chooser falls back to the host sort-probe, and asking for a snapshot —
    which only the device route can pin — fails loudly at compile time."""
    from repro.core.plan import PlanError

    char_schema = benchmark_schema(64, 8)  # char columns
    wide = RelationalTable.from_columns(
        char_schema,
        {c.name: np.full(8, b"x", dtype="S8") for c in char_schema.columns},
    )
    eng = RelationalMemoryEngine()
    q = plan(wide).join(wide, key="A2", left_proj="A1", right_proj="A3")
    shape = decompose(q)
    assert not planner._device_join_expressible(shape)
    assert planner._join_route(eng, shape, None) == "shared-scan-join"
    with pytest.raises(PlanError):
        compile_plan(eng, q, snapshot_ts=0)
