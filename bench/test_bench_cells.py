"""CPU rehearsal of every benchmark cell: the whole run, set-up, window and
reference check, through ``QueryServer`` at a small size with the Pallas
kernels in interpret mode.  Nothing here calls the TPU compiler or reads a
TPU topology; the run's look for a chip is skipped."""

from __future__ import annotations

import json
import time

import pytest

from bench import check, harness

ROWS = 4096
SEED = 2**31 + 4321  # above 32 signed bits, as a run's --seed may be
CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def run_small(workload: str, monkeypatch, seconds: float = 2.0) -> dict:
    # keep this process's JAX configuration as the other tests expect it
    monkeypatch.setattr(harness, "configure_compile_cache", lambda: None)
    return harness.run(workload, SEED, seconds, False,
                       t0=time.perf_counter(), rows=ROWS,
                       require_accelerator=False)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_on_cpu(workload, monkeypatch):
    r = run_small(workload, monkeypatch)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(check.LIMITS)
    assert r["failed"] == 0 and r["attempted"] >= 8
    cell = harness.Cell.load(workload)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert r["metrics"][m["name"]]["unit"] == m["unit"]
        assert r["metrics"][m["name"]]["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1


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
