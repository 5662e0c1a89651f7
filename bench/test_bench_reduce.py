"""The metric arithmetic of ``bench/reduce.py`` on synthetic numbers, and
the trace reduction on a trace recorded on the chip (``bench/fixtures``)."""

from __future__ import annotations

import dataclasses
import pathlib
import random
import statistics

import pytest

from bench import reduce


def test_percentile_matches_statistics():
    rnd = random.Random(3)
    for n in (2, 7, 100, 1001):
        xs = [rnd.expovariate(1.0) for _ in range(n)]
        want = statistics.quantiles(xs, n=100, method="inclusive")
        for q in (50, 95, 99):
            assert reduce.percentile(xs, q) == pytest.approx(want[q - 1])
    assert reduce.percentile([4.0], 95) == 4.0


def test_union_merges_overlaps():
    assert reduce.union_ns([(0, 10), (5, 15), (20, 30), (30, 31)]) == 26
    assert reduce.union_ns([]) == 0


@dataclasses.dataclass
class _Read:
    admitted_at: float
    route: str
    probe_words: frozenset
    build_words: frozenset = frozenset()
    result_bytes: int = 0


def test_ticks_and_need_bytes():
    reads = [
        _Read(2.0, "fused-aggregate", frozenset({0, 3})),
        _Read(1.0, "rme", frozenset({1}), result_bytes=40),
        _Read(1.0, "device-hash-join", frozenset({1, 3}), frozenset({1, 2}),
              result_bytes=90),
        _Read(1.0, "hot", frozenset({7, 8}), result_bytes=1000),
    ]
    ticks = reduce.group_ticks(reads)
    assert [len(t) for t in ticks] == [3, 1]
    # tick 1: probe words {1, 3} of 10 rows, build words {1, 2} of 5 rows;
    # the cache hit runs no kernel and is left out
    assert reduce.tick_need_bytes(ticks[0], 10, 5) == 10 * 4 * 2 + 5 * 4 * 2 + 130
    assert reduce.tick_need_bytes(ticks[1], 10, 5) == 10 * 4 * 2
    with pytest.raises(ValueError, match="not known"):
        reduce.tick_need_bytes([_Read(0.0, "new-route", frozenset())], 1, 1)


def _kernel(name):
    return (f'%{name} = s32[8,1] custom-call(s32[8,18] %copy), '
            'custom_call_target="tpu_custom_call"')


def _synthetic_trace():
    E = reduce.Event
    ops = [E("%copy.3 = s32[8,18] copy(s32[8,18] %words)", 0, 10),
           E(_kernel("_scan_multi.1"), 10, 30),
           E("%copy.4 = s32[8,1] copy(s32[8,1] %x)", 35, 5),
           E("%fusion = s32[8] fusion(s32[8] %y), kind=kLoop", 60, 20),
           E(_kernel("_hash_join.2"), 90, 10),
           E('%custom-call.2 = s32[8,4] custom-call(s32[2,4] %a), '
             'custom_call_target="ConcatBitcast"', 100, 0)]
    host = [E("bench.traced", 0, 120), E("PjitFunction(x)", 38, 20),
            E("bench.wait", 80, 30)]
    return reduce.Trace({"/device:TPU:0": ops}, host)


def test_device_time_kernel_and_glue():
    tr = _synthetic_trace()
    dt = reduce.device_time(tr, 0, 120, chips=1)
    assert dt.busy_ns == 70 and dt.window_ns == 120
    assert dt.kernel_ns == 40 and dt.glue_ns == 35
    assert dt.busy_ns_per_chip == [70] and dt.kernel_ns_per_chip == [40]
    clipped = reduce.device_time(tr, 20, 70, chips=1)
    assert clipped.busy_ns == 20 + 10 and clipped.kernel_ns == 20


def test_breakdown_names_ops_and_gaps():
    tr = _synthetic_trace()
    top = reduce.top_ops(tr, 0, 120)
    assert top[0] == ["_scan_multi", pytest.approx(30e-9)]
    assert ["_hash_join", pytest.approx(10e-9)] in top
    assert ["copy", pytest.approx(15e-9)] in top
    gaps = reduce.idle_gaps(tr, 0, 120, ignore=("bench.traced",))
    assert gaps[0] == ["PjitFunction(x)", pytest.approx(20e-9)]
    assert ["bench.wait", pytest.approx(20e-9)] in gaps


def _chip_trace():
    """Two rounds of ``rm256.analytic`` at 2^24 rows (16 row ranges) traced
    on one TPU v5e by a ``--trace 1`` run of this benchmark (seed 2002)."""
    import lzma

    import jax

    path = pathlib.Path(__file__).parent / "fixtures" / "rm256_trace.xplane.pb.xz"
    return reduce.load_trace(jax.profiler.ProfileData.from_serialized_xspace(
        lzma.decompress(path.read_bytes())))


def test_recorded_chip_trace_reduces():
    tr = _chip_trace()
    assert list(tr.device_ops) == ["/device:TPU:0"]
    span = tr.annotation("bench.traced")
    lo, hi = span.start_ns, span.end_ns
    dt = reduce.device_time(tr, lo, hi, chips=1)
    # what the run reported as busy_s and window_s
    assert dt.window_ns * 1e-9 == pytest.approx(4.973447503, rel=1e-12)
    assert dt.busy_ns * 1e-9 == pytest.approx(4.7458896180000005, rel=1e-12)
    assert dt.busy_ns_per_chip == [dt.busy_ns]
    # the kernels: 32 fused scans and 32 join probes over two rounds of
    # 16 row ranges; everything else is glue
    ops = tr.device_ops["/device:TPU:0"]
    kernels = [reduce.op_name(e.name) for e in ops if reduce.is_kernel(e.name)]
    assert sorted(set(kernels)) == ["_hash_join", "_scan_multi"]
    assert len(kernels) == 64
    assert dt.kernel_ns + dt.glue_ns >= dt.busy_ns
    assert dt.kernel_ns * 1e-9 == pytest.approx(4.489652295, rel=1e-9)
    assert dt.kernel_ns_per_chip == [dt.kernel_ns]
    top = reduce.top_ops(tr, lo, hi)
    assert [name for name, _ in top[:3]] == ["_hash_join", "copy", "_scan_multi"]
    gaps = reduce.idle_gaps(tr, lo, hi, ignore=("bench.traced",))
    assert len(gaps) == 10
    assert sum(s for _, s in gaps) < (dt.window_ns - dt.busy_ns) * 1e-9


def test_read_qps_counts_every_answer_over_its_span():
    """Reads answered per second: every answered read of the window over
    the seconds until the last answer, not over the nominal window."""
    import importlib

    from bench import harness

    qps = importlib.import_module("bench.metrics.read_qps")
    reads = [_Read(1.0, "rme", frozenset()) for _ in range(80)]
    w = harness.Window(setup_s=1.0, seconds=49.2, reads=reads,
                       engine_delta={})
    assert qps.read(w) == pytest.approx(80 / 49.2)
    assert qps.read(harness.Window(1.0, 48.0, [], {})) is None
