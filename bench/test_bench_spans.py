"""The span reduction of ``bench/spans.py`` on a synthetic trace whose idle
split is known, and on the trace recorded on the chip (``bench/fixtures``),
which holds no program spans."""

from __future__ import annotations

import types

import pytest

from bench import harness, reduce, spans

MS = 1e6  # nanoseconds


def _event(name, start_ms, end_ms, **stats):
    return types.SimpleNamespace(name=name, start_ns=start_ms * MS,
                                 duration_ns=(end_ms - start_ms) * MS,
                                 stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


def _xspace(chips: int = 1, root_last: bool = False):
    """A window of 1000 ms.  Each of ``chips`` devices is busy 100-200,
    300-400 and 600-900 ms, so idle 500 ms: 100 of it inside an engine span
    (200-300), 270 inside server or planner spans with no engine span open
    (50-100, 400-450, 480-600, 900-950), and 130 with no program span open
    (0-50, 450-480, 950-1000).  With ``root_last`` the device planes are
    listed from the last chip down to chip 0, and every chip but chip 0 is
    also busy 950-1000 ms."""
    devices = [_plane(f"/device:TPU:{i}", [(reduce.DEVICE_OPS_LINE, [
        _event("%fusion.1 = s32[8] fusion(s32[8] %a)", 100, 200),
        _event("%copy.2 = s32[8] copy(s32[8] %b)", 300, 400),
        _event('%k.3 = s32[8] custom-call(s32[8] %c), '
               'custom_call_target="tpu_custom_call"', 600, 900),
        *([_event("%copy.4 = s32[8] copy(s32[8] %d)", 950, 1000)]
          if root_last and i else [])])])
        for i in range(chips)]
    if root_last:
        devices.reverse()
    serving = [
        _event("server.tick", 50, 450, tick=1),
        _event("planner.compile_plan", 60, 90, tick=1, ticket=1,
               route="fused-aggregate"),
        _event("planner.compile_plan", 90, 130, tick=1, ticket=2,
               route="rme"),
        _event("engine.execute_many", 150, 350, tick=1, ops=2),
        _event("engine.scan_multi", 160, 260, chunk=0, range=0, rows=8),
        _event("PjitFunction(convert_element_type)", 170, 175),
        _event("PjitFunction(convert_element_type)", 171, 174),
        _event("DevicePut", 180, 181),
        _event("server.finish_tick", 480, 950, tick=1),
        _event("server.finalize", 520, 580, tick=1, ticket=2, lane="bulk"),
        _event("PjitFunction(concatenate)", 530, 540),
        _event("PjitFunction(add)", 1005, 1006),
    ]
    client = [_event("bench.traced", 0, 1000),
              _event("engine.combine", 455, 470),  # not the serving thread
              _event("ExecutePrepare", 410, 590)]
    host = _plane(reduce.HOST_PLANE, [("python3", client),
                                      ("python3", serving)])
    return types.SimpleNamespace(planes=[*devices, host])


def test_idle_split_partitions_idle_time():
    got = spans.reduce_spans(_xspace(), ticks=2)
    m = got["metrics"]
    assert got["idle_s"] == pytest.approx(0.5)
    assert m["idle_dispatch_ms_per_tick"] == pytest.approx(100 / 2)
    assert m["idle_frontend_ms_per_tick"] == pytest.approx(270 / 2)
    assert m["idle_between_ticks_ms_per_tick"] == pytest.approx(130 / 2)
    assert m["plan_ms_per_read"] == pytest.approx((30 + 40) / 2)


def test_span_totals_per_tick():
    """Every serving-thread span's inclusive time and count per tick, over
    the spans that begin in the window: the client's ``engine.combine`` and
    the eager calls are not among them.  ``planner.compile_plan``'s time
    over its count is ``plan_ms_per_read``."""
    m = spans.reduce_spans(_xspace(), ticks=2)["metrics"]
    ms = {"server.tick": 400, "planner.compile_plan": 30 + 40,
          "engine.execute_many": 200, "engine.scan_multi": 100,
          "server.finish_tick": 470, "server.finalize": 60}
    count = dict.fromkeys(ms, 1) | {"planner.compile_plan": 2}
    assert m["span_ms_per_tick"] == pytest.approx(
        {k: v / 2 for k, v in ms.items()})
    assert m["span_count_per_tick"] == {k: v / 2 for k, v in count.items()}
    plan = "planner.compile_plan"
    assert (m["span_ms_per_tick"][plan] / m["span_count_per_tick"][plan]
            == pytest.approx(m["plan_ms_per_read"], rel=1e-12))
    # only the spans that begin in the window count, each with its whole time
    m = spans.metrics(spans.serving_thread(_xspace()).spans.spans, [],
                      0.0, 100 * MS, 1)
    assert m["span_count_per_tick"] == {"server.tick": 1,
                                        "planner.compile_plan": 2}
    assert m["span_ms_per_tick"] == pytest.approx(
        {"server.tick": 400, "planner.compile_plan": 70})


def test_idle_metrics_sum_to_the_idle_share():
    """The three idle metrics add up to ``device_idle_share`` / 100 x the
    traced window / ticks, as ``bench/metrics`` reads the same trace."""
    xs = _xspace()
    tr = reduce.load_trace(xs)
    lo, hi = 0.0, 1000 * MS
    dt = reduce.device_time(tr, lo, hi, chips=1)
    share = 100.0 * (1.0 - dt.busy_ns / dt.window_ns)
    for ticks in (1, 3):
        m = spans.reduce_spans(xs, ticks)["metrics"]
        total = sum(v for k, v in m.items() if k.startswith("idle_"))
        assert total == pytest.approx(
            share / 100 * dt.window_ns * 1e-6 / ticks, rel=1e-12)


def test_gaps_name_the_innermost_span():
    gaps = spans.reduce_spans(_xspace(), ticks=1)["gaps"]
    assert [round(g["seconds"], 6) for g in gaps] == [0.2, 0.1, 0.1, 0.1]
    longest = gaps[0]
    assert longest["at_s"] == pytest.approx(0.4)
    assert longest["span"] == "server.finish_tick"
    assert longest["args"] == {"tick": 1}
    assert longest["runtime"] == "ExecutePrepare"
    assert longest["host_events"] == ["ExecutePrepare"]
    by_at = {round(g["at_s"], 6): g for g in gaps}
    assert by_at[0.2]["span"] == "engine.scan_multi"
    assert by_at[0.2]["args"] == {"chunk": 0, "range": 0, "rows": 8}
    assert by_at[0.0]["span"] == "server.tick"  # the gap's middle, 50 ms
    assert by_at[0.9]["span"] == "server.finish_tick"  # open to 950 ms


def test_idle_by_innermost_span():
    got = spans.reduce_spans(_xspace(), ticks=2)["idle_ms_per_tick_by_span"]
    want = {"none": 130, "server.finish_tick": 110, "server.tick": 60,
            "engine.scan_multi": 60, "server.finalize": 60,
            "planner.compile_plan": 40, "engine.execute_many": 40}
    assert got == pytest.approx({k: v / 2 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(500 / 2)


def test_dispatches_count_outermost_calls_by_span():
    d = spans.reduce_spans(_xspace(), ticks=1)["dispatches"]
    # the nested PjitFunction is one program with its caller; the call
    # after the window is not counted
    assert d["by_span"] == {"engine.scan_multi": [1, 1],
                            "server.finalize": [1, 0]}
    assert d["by_call"]["engine.scan_multi DevicePut"] == 1


def test_serving_thread_is_the_line_with_ticks():
    thread = spans.serving_thread(_xspace())
    names = [s.name for s in thread.spans.spans]
    assert "engine.combine" not in names  # the client's line
    assert names[0] == "server.tick"
    assert thread.spans.innermost(165 * MS).name == "engine.scan_multi"
    assert thread.spans.innermost(300 * MS).name == "engine.execute_many"
    assert thread.spans.innermost(460 * MS) is None


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [(5, 15)], 5),
    ([(0, 10), (20, 30)], [(5, 25)], 10),
    ([(0, 10)], [], 0),
    ([(0, 1), (2, 3), (4, 5)], [(0, 5)], 3),
])
def test_overlap_of_interval_lists(a, b, want):
    assert spans.overlap(spans.merge(a), spans.merge(b)) == want


def test_recorded_chip_trace_has_only_between_tick_idle():
    """The fixture predates the program's spans: all its idle time is
    between ticks, and the split still sums to the idle share."""
    import lzma
    import pathlib

    import jax

    path = (pathlib.Path(__file__).parent / "fixtures"
            / "rm256_trace.xplane.pb.xz")
    xs = jax.profiler.ProfileData.from_serialized_xspace(
        lzma.decompress(path.read_bytes()))
    got = spans.reduce_spans(xs, ticks=2)
    tr = reduce.load_trace(xs)
    window = tr.annotation("bench.traced")
    dt = reduce.device_time(tr, window.start_ns, window.end_ns, chips=1)
    idle_ms = (dt.window_ns - dt.busy_ns) * 1e-6
    m = got["metrics"]
    assert got["spans"] == 0 and "plan_ms_per_read" not in m
    assert m["idle_dispatch_ms_per_tick"] == 0
    assert m["idle_frontend_ms_per_tick"] == 0
    assert m["idle_between_ticks_ms_per_tick"] == pytest.approx(
        idle_ms / 2, rel=1e-9)
    assert len(got["gaps"]) == 10


def test_span_cost_is_timed_with_and_without_a_profiler():
    cost = spans.span_cost_us(n=200)
    assert cost["spans"] == 200
    assert cost["off_us"] > 0 and cost["on_us"] > 0


def test_script_needs_a_run_or_the_span_cost(capsys):
    assert spans.main(["--workload", "rm64.analytic"]) == 2
    assert "--seed" in capsys.readouterr().err


def test_recorded_chip_trace_with_spans():
    """Three rounds of ``rm256.analytic`` (2^23 rows, 8 row ranges) traced
    on one TPU v5e by ``bench/spans.py`` (seed 3200000003): the numbers it
    printed there, the sum rule, and the kernels under their names."""
    import lzma
    import pathlib

    import jax

    path = (pathlib.Path(__file__).parent / "fixtures"
            / "rm256_spans.xplane.pb.xz")
    xs = jax.profiler.ProfileData.from_serialized_xspace(
        lzma.decompress(path.read_bytes()))
    got = spans.reduce_spans(xs, ticks=3)
    m = got["metrics"]
    assert m["idle_dispatch_ms_per_tick"] == pytest.approx(55.64445533333333)
    assert m["idle_frontend_ms_per_tick"] == pytest.approx(8.823521999999999)
    assert m["idle_between_ticks_ms_per_tick"] == pytest.approx(
        2.0627733333333333)
    assert m["plan_ms_per_read"] == pytest.approx(0.09259454166666665)
    plan = "planner.compile_plan"
    assert m["span_count_per_tick"][plan] == 8
    assert (m["span_ms_per_tick"][plan] / m["span_count_per_tick"][plan]
            == pytest.approx(m["plan_ms_per_read"], rel=1e-12))
    assert m["span_count_per_tick"]["engine.serve_scan"] == 1
    assert m["span_ms_per_tick"]["server.tick"] > m["span_ms_per_tick"][
        "engine.execute_many"] > m["span_ms_per_tick"]["engine.serve_scan"]
    tr = reduce.load_trace(xs)
    window = tr.annotation("bench.traced")
    lo, hi = window.start_ns, window.end_ns
    dt = reduce.device_time(tr, lo, hi, chips=1)
    assert dt.busy_ns_per_chip == [dt.busy_ns]
    assert dt.kernel_ns_per_chip == [dt.kernel_ns]
    share = 100.0 * (1.0 - dt.busy_ns / dt.window_ns)  # device_idle_share
    idle = sum(v for k, v in m.items() if k.startswith("idle_"))
    assert idle == pytest.approx(share / 100 * dt.window_ns * 1e-6 / 3,
                                 rel=1e-9)
    assert sum(got["idle_ms_per_tick_by_span"].values()) == pytest.approx(
        idle)
    # 8 reads a tick: a compile, a launch and a finalize each; 8 row ranges:
    # a slice and a kernel call each for the scan and for the join probe
    names = [s.name for s in spans.serving_thread(xs).spans.spans
             if lo <= s.start_ns <= hi]
    assert names.count("planner.compile_plan") == 3 * 8
    assert names.count("engine.scan_multi") == 3 * 8
    assert names.count("engine.hash_join") == 3 * 8
    assert names.count("engine.row_slice") == 3 * 16
    kernels = {name for name, _ in reduce.top_ops(tr, lo, hi)
               if name.startswith("rme_")}
    assert kernels == {"rme_hash_join", "rme_scan_multi"}
    # a fused-pass call: 31 eager programs and 16 transfers a row range
    assert got["dispatches"]["by_span"]["engine.scan_multi"] == [
        3 * 8 * 31, 3 * 8 * 16]


PROBE_ROWS, BUILD_ROWS = 1 << 20, 1 << 16
V5E = {"hbm_bytes_per_s": 819e9}


def _traced_window(chips: int = 1, ticks: int = 2) -> harness.Window:
    """The synthetic trace as the harness hands it to the readers: ``ticks``
    ticks of one kernel-served read each, reading probe words 0 and 1 and
    build word 1, 100 result bytes."""
    recs = [harness.ReadRecord(None, 0.0, t_ready=1.0, admitted_at=float(i),
                               route="rme", result_bytes=100,
                               probe_words=frozenset({0, 1}),
                               build_words=frozenset({1}))
            for i in range(ticks)]
    traced = harness.reduce_traced(_xspace(chips), recs, chips, PROBE_ROWS,
                                   BUILD_ROWS, V5E)
    return harness.Window(1.0, 1.0, recs, {}, traced)


def _read(name, w):
    return harness._read_metric(name, w)


def test_span_metrics_reach_the_readers():
    """The harness reduces the spans before the trace goes, and each of the
    four per-layer readers finds its number; the three idle metrics sum to
    the idle share read from the same window."""
    w = _traced_window(ticks=2)
    assert _read("idle_dispatch_ms_per_tick", w) == pytest.approx(100 / 2)
    assert _read("idle_frontend_ms_per_tick", w) == pytest.approx(270 / 2)
    assert _read("idle_between_ticks_ms_per_tick", w) == pytest.approx(
        130 / 2)
    assert _read("plan_ms_per_read", w) == pytest.approx((30 + 40) / 2)
    assert w.traced.spans["span_ms_per_tick"][
        "planner.compile_plan"] == pytest.approx((30 + 40) / 2)
    assert w.traced.spans["span_count_per_tick"]["engine.scan_multi"] == 0.5
    idle = sum(_read(f"idle_{k}_ms_per_tick", w)
               for k in ("dispatch", "frontend", "between_ticks"))
    share = _read("device_idle_share", w)
    assert share == pytest.approx(50.0)
    assert idle == pytest.approx(
        share / 100 * w.traced.device.window_ns * 1e-6 / 2, rel=1e-12)


def test_span_metrics_absent_without_a_trace():
    w = harness.Window(1.0, 1.0, [], {})
    for name in ("idle_dispatch_ms_per_tick", "idle_frontend_ms_per_tick",
                 "idle_between_ticks_ms_per_tick", "plan_ms_per_read"):
        assert _read(name, w) is None


@pytest.mark.parametrize("chips", [1, 4])
def test_hbm_roofline_over_the_chips(chips):
    """Each of ``chips`` device planes is busy 500 ms of the 1000 ms window;
    the need of two ticks is met at ``chips`` x 819 GB/s over that busy
    time, the mean over the chips."""
    w = _traced_window(chips)
    need = 2 * (PROBE_ROWS * 4 * 2 + BUILD_ROWS * 4 * 1 + 100)
    assert w.traced.need_bytes == need
    assert w.traced.device.busy_ns == pytest.approx(500 * MS)
    per_chip = w.traced.device.busy_ns_per_chip
    assert len(per_chip) == chips
    assert sum(per_chip) / chips == w.traced.device.busy_ns
    assert sum(w.traced.device.kernel_ns_per_chip) / chips == (
        w.traced.device.kernel_ns)
    assert w.traced.hbm_bytes_per_s == chips * 819e9
    want = 100.0 * need / (chips * 819e9) / 0.5
    assert _read("hbm_roofline", w) == pytest.approx(want, rel=1e-12)
    assert _read("device_idle_share", w) == pytest.approx(50.0)
    # the idle split and the idle gaps stay the root chip's
    assert _read("idle_dispatch_ms_per_tick", w) == pytest.approx(100 / 2)
    assert len(w.traced.breakdown["idle_gaps"]) == 4


@pytest.mark.parametrize("chips, root_last", [(1, False), (4, False),
                                              (4, True)])
def test_root_chip_is_read_by_name(chips, root_last):
    """The busy intervals, the idle gaps and the idle split are chip 0's,
    whichever plane the trace lists first: with ``root_last`` the first
    plane is chip 3's, which is busy 950-1000 ms where chip 0 is idle."""
    xs = _xspace(chips, root_last)
    tr = reduce.load_trace(xs)
    assert (next(iter(tr.device_ops)) == reduce.ROOT_PLANE) != root_last
    lo, hi = 0.0, 1000 * MS
    assert spans.busy_intervals(tr, lo, hi) == pytest.approx(
        [(100 * MS, 200 * MS), (300 * MS, 400 * MS), (600 * MS, 900 * MS)])
    gaps = reduce.idle_gaps(tr, lo, hi, ignore=("bench.traced",))
    assert [round(g, 6) for _, g in gaps] == [0.2, 0.1, 0.1, 0.1]
    m = spans.reduce_spans(xs, ticks=2)["metrics"]
    assert m["idle_dispatch_ms_per_tick"] == pytest.approx(100 / 2)
    assert m["idle_frontend_ms_per_tick"] == pytest.approx(270 / 2)
    assert m["idle_between_ticks_ms_per_tick"] == pytest.approx(130 / 2)
    # each chip's busy time: chip 0's first, then in device order
    dt = reduce.device_time(tr, lo, hi, chips)
    others = 550 * MS if root_last else 500 * MS
    assert dt.busy_ns_per_chip == [500 * MS] + [others] * (chips - 1)
    assert dt.kernel_ns_per_chip == [300 * MS] * chips
    assert sum(dt.busy_ns_per_chip) / chips == pytest.approx(dt.busy_ns,
                                                             rel=1e-15)


@pytest.mark.parametrize("chips", [1, 2])
def test_only_the_cells_chips_are_read(chips):
    """A cell of ``chips`` chips on a host of four, whose trace lists all
    four planes from chip 3 down: the per-chip times are those of chips
    0 to ``chips - 1`` alone, in device order, and their mean is
    ``busy_ns``.  Chips 1-3 are busy 550 ms, chip 0 500 ms."""
    tr = reduce.load_trace(_xspace(4, root_last=True))
    dt = reduce.device_time(tr, 0.0, 1000 * MS, chips)
    assert dt.busy_ns_per_chip == [500 * MS, 550 * MS][:chips]
    assert dt.kernel_ns_per_chip == [300 * MS] * chips
    assert dt.busy_ns == sum(dt.busy_ns_per_chip) / chips
    assert dt.glue_ns == pytest.approx(
        (200 * MS + 250 * MS * (chips - 1)) / chips)


def test_trace_without_a_cells_chip_is_refused():
    xs = _xspace(4)
    del xs.planes[1]  # chip 1's plane
    tr = reduce.load_trace(xs)
    assert reduce.device_time(tr, 0.0, 1000 * MS, 1).busy_ns == 500 * MS
    with pytest.raises(ValueError, match="TPU:1"):
        reduce.device_time(tr, 0.0, 1000 * MS, 2)


def test_trace_without_the_root_plane_is_refused():
    xs = _xspace(2)
    xs.planes = xs.planes[1:]  # chip 1's plane and the host's
    tr = reduce.load_trace(xs)
    with pytest.raises(ValueError, match="TPU:0"):
        reduce.idle_gaps(tr, 0.0, 1000 * MS)
    with pytest.raises(ValueError, match="TPU:0"):
        spans.busy_intervals(tr, 0.0, 1000 * MS)
    with pytest.raises(ValueError, match="TPU:0"):
        reduce.device_time(tr, 0.0, 1000 * MS, 2)
