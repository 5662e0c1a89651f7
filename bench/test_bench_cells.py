"""CPU rehearsal of every benchmark cell: the whole run, set-up, window and
reference check, through ``QueryServer`` at a small size with the Pallas
kernels in interpret mode.  Nothing here calls the TPU compiler or reads a
TPU topology; the run's look for a chip is skipped."""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from bench import check, harness

ROWS = 4096
SEED = 2**31 + 4321  # above 32 signed bits, as a run's --seed may be
WORKLOADS = json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]
CELLS = [w["name"] for w in WORKLOADS]
SHARDS = 4
# every cell on its own chip count, and rm64.analytic served on four shards
CASES = [pytest.param(w["name"], w["chips"], id=w["name"]) for w in WORKLOADS]
CASES.append(pytest.param("rm64.analytic", SHARDS,
                          id=f"rm64.analytic-{SHARDS}-chips"))


def run_small(workload: str, setattr, chips: int | None = None,
              seconds: float = 2.0) -> dict:
    """``workload`` at a small size, on ``chips`` chips where given in place
    of the cell's own.  ``setattr`` patches: ``monkeypatch.setattr`` in a
    test, the builtin in a child process."""
    # keep this process's JAX configuration as the other tests expect it
    setattr(harness, "configure_compile_cache", lambda: None)
    if chips is not None:
        load = harness.Cell.load
        setattr(harness.Cell, "load", staticmethod(
            lambda w: dataclasses.replace(load(w), chips=chips)))
    return harness.run(workload, SEED, seconds, False,
                       t0=time.perf_counter(), rows=ROWS,
                       require_accelerator=False)


def run_child(code: str, devices: int,
              cwd=harness.ROOT) -> subprocess.CompletedProcess:
    """``python -c code`` on ``devices`` CPU devices, importing ``bench``
    from ``cwd``; its last line of standard output is its answer."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    env["PYTHONPATH"] = os.pathsep.join([str(cwd), str(harness.ROOT / "src")])
    out = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out


def _upload_devices(stderr: str) -> list[list[str]]:
    """Each resident chunk's devices, from the harness's upload line."""
    prefix = "upload: shard rows on devices "
    (line,) = [x for x in stderr.splitlines() if x.startswith(prefix)]
    return [devs for _, devs in json.loads(line[len(prefix):])]


@pytest.mark.parametrize("workload, chips", CASES)
def test_cell_runs_correct_on_cpu(workload, chips, monkeypatch):
    """The whole run of a cell on ``chips`` devices.  One chip runs in this
    process; more run in a child process with that many CPU devices, where
    each shard's rows sit on a chip of their own."""
    if chips == 1:
        r = run_small(workload, monkeypatch.setattr, chips)
    else:
        out = run_child(
            "import json; from bench import test_bench_cells as t; "
            f"print(json.dumps(t.run_small({workload!r}, setattr, {chips})))",
            chips)
        r = json.loads(out.stdout.splitlines()[-1])
        placed = _upload_devices(out.stderr)
        assert len(placed) == chips and all(len(d) == 1 for d in placed)
        assert len({d[0] for d in placed}) == chips
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(check.LIMITS)
    assert r["failed"] == 0 and r["attempted"] >= 8
    cell = harness.Cell.load(workload)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert r["metrics"][m["name"]]["unit"] == m["unit"]
        assert r["metrics"][m["name"]]["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == chips
    per_chip = r["device"]["memory_peak_bytes_per_chip"]
    assert len(per_chip) == chips
    assert r["device"]["memory_peak_bytes"] == max(per_chip)


def test_no_accelerator_is_refused(monkeypatch):
    monkeypatch.setattr(harness, "configure_compile_cache", lambda: None)
    with pytest.raises(harness.NoAccelerator):
        harness.run(CELLS[0], SEED, 1.0, False, t0=time.perf_counter(),
                    rows=ROWS)


def test_cell_metadata_is_complete():
    """Every cell finds its configuration, mix and metric readers by name."""
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.Cell.load(w["name"])
        assert cell.mix.templates and cell.config["rows"] > 0
        assert (harness.BENCH / "clients" / f"{cell.mix.clients}.py").is_file()
        for m in cell.end_to_end + cell.per_layer:
            assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_sharded_cell_needs_its_chips(monkeypatch):
    """A four-chip cell refuses to start on this process's one device, even
    where the look for a TPU is skipped: it never shards onto fewer."""
    with pytest.raises(harness.NoAccelerator, match="needs 4"):
        run_small("rm64.analytic", monkeypatch.setattr, SHARDS)


def test_every_engine_counter_reaches_the_readers():
    """``Window.engine_delta`` holds the window delta of every integer
    counter of ``EngineStats``, by its field name; the gauge
    ``last_block_rows`` is left out."""
    from types import SimpleNamespace

    from repro.core.engine import EngineStats

    names = {f.name for f in dataclasses.fields(EngineStats)}
    engine = SimpleNamespace(stats=EngineStats())
    before = harness.engine_counters(engine)
    assert set(before) == names - {"last_block_rows"} == (
        names - harness.ENGINE_GAUGES)
    for i, name in enumerate(sorted(names)):
        setattr(engine.stats, name, i + 1)
    after = harness.engine_counters(engine)
    want = {name: i + 1 for i, name in enumerate(sorted(names))
            if name != "last_block_rows"}
    assert {k: after[k] - before[k] for k in after} == want
    w = harness.Window(1.0, 1.0, [], {"kernel_fallbacks": 3, "failovers": 2})
    assert harness._read_metric("kernel_fallbacks", w) == 3


SPAN_FIXTURE = harness.BENCH / "fixtures" / "rm256_spans.xplane.pb.xz"
NEW_CELL = {"name": "rm64x4.analytic", "config": "rm64x4",
            "traffic": "paper_q", "chips": SHARDS,
            "why": "2^27 rows of rm64 over four chips"}
NEW_METRIC = {"name": "shard_probe_check", "unit": "ms/ms",
              "better": "lower", "source": "device_trace",
              "layer": "engine dispatch (core/engine.py)",
              "moves": "read_qps", "workloads": [NEW_CELL["name"]]}
NEW_READER = '''"""Failovers in the window, plus the serving thread's engine.serve_scan
time a tick over the busiest chip's busy time a tick."""


def read(w):
    t = w.traced
    if t is None:
        return None
    busiest_ms = max(t.device.busy_ns_per_chip) * 1e-6 / t.ticks
    return (w.engine_delta["failovers"]
            + t.spans["span_ms_per_tick"]["engine.serve_scan"] / busiest_ms)
'''
FAILOVERS = 2


def read_new_metric(fixture: str) -> dict:
    """In a checkout that adds ``NEW_CELL`` and ``NEW_METRIC``: load the
    cell, and read the metric from a window whose engine failed over
    ``FAILOVERS`` times and whose trace is the three ticks of ``fixture``
    (``SPAN_FIXTURE``)."""
    import lzma
    from types import SimpleNamespace

    import jax

    from repro.core.engine import EngineStats

    cell = harness.Cell.load(NEW_CELL["name"])
    engine = SimpleNamespace(stats=EngineStats())
    before = harness.engine_counters(engine)
    engine.stats.failovers += FAILOVERS
    after = harness.engine_counters(engine)
    recs = [harness.ReadRecord(None, 0.0, t_ready=1.0, admitted_at=float(i),
                               route="rme", probe_words=frozenset({0}))
            for i in range(3)]
    xs = jax.profiler.ProfileData.from_serialized_xspace(
        lzma.decompress(pathlib.Path(fixture).read_bytes()))
    traced = harness.reduce_traced(xs, recs, 1, 1 << 23, 1 << 16,
                                   {"hbm_bytes_per_s": 819e9})
    w = harness.Window(1.0, 1.0, recs,
                       {k: after[k] - before[k] for k in after}, traced)
    return {"root": str(harness.ROOT), "chips": cell.chips,
            "per_layer": [m["name"] for m in cell.per_layer],
            "value": harness._read_metric(NEW_METRIC["name"], w)}


def test_a_new_cell_and_reader_need_no_other_edit(tmp_path):
    """A copy of the benchmark that adds only a four-chip cell on ``rm64``
    (its configuration file and its entry) and one metric file: the harness
    loads the cell and the reader, and the reader gets a window delta of
    ``failovers``, the per-tick time of the ``engine.serve_scan`` span and
    each chip's busy time.  On the fixture's one chip, that busy time is
    the mean's."""
    import lzma
    import shutil

    import jax

    from bench import reduce, spans

    checkout = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, checkout / "bench", ignore=(
        shutil.ignore_patterns("__pycache__", "fixtures")))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append(NEW_CELL)
    spec["per_layer"].append(NEW_METRIC)
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    config = json.loads((harness.BENCH / "configs" / "rm64.json").read_text())
    (checkout / "bench" / "configs" / "rm64x4.json").write_text(
        json.dumps({**config, "rows": 1 << 27}))
    (checkout / "bench" / "metrics" / f"{NEW_METRIC['name']}.py").write_text(
        NEW_READER)
    out = run_child(
        "import json; from bench import test_bench_cells as t; print("
        f"json.dumps(t.read_new_metric({str(SPAN_FIXTURE)!r})))", 1,
        cwd=checkout)
    got = json.loads(out.stdout.splitlines()[-1])
    assert got["root"] == str(checkout)
    assert got["chips"] == SHARDS
    assert NEW_METRIC["name"] in got["per_layer"]

    xs = jax.profiler.ProfileData.from_serialized_xspace(
        lzma.decompress(SPAN_FIXTURE.read_bytes()))
    tr = reduce.load_trace(xs)
    window = tr.annotation("bench.traced")
    lo, hi = window.start_ns, window.end_ns
    scans = [s for s in spans.serving_thread(xs).spans.spans
             if s.name == "engine.serve_scan" and lo <= s.start_ns <= hi]
    assert len(scans) == 3
    scan_ms = sum(s.dur_ns for s in scans) * 1e-6 / 3
    dt = reduce.device_time(tr, lo, hi, chips=1)
    assert dt.busy_ns_per_chip == [dt.busy_ns]
    assert got["value"] == pytest.approx(
        FAILOVERS + scan_ms / (dt.busy_ns * 1e-6 / 3), rel=1e-12)
