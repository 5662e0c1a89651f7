"""CPU rehearsal of every benchmark cell: the whole run, set-up, window and
reference check, through ``QueryServer`` at a small size with the Pallas
kernels in interpret mode.  Nothing here calls the TPU compiler or reads a
TPU topology; the run's look for a chip is skipped."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

from bench import check, harness

ROWS = 4096
SEED = 2**31 + 4321  # above 32 signed bits, as a run's --seed may be
CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def run_small(workload: str, setattr, seconds: float = 2.0) -> dict:
    """``workload`` at a small size.  ``setattr`` patches:
    ``monkeypatch.setattr`` in a test, the builtin in a child process."""
    # keep this process's JAX configuration as the other tests expect it
    setattr(harness, "configure_compile_cache", lambda: None)
    return harness.run(workload, SEED, seconds, False,
                       t0=time.perf_counter(), rows=ROWS,
                       require_accelerator=False)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_on_cpu(workload, monkeypatch):
    r = run_small(workload, monkeypatch.setattr)
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


SHARDS = 4


def run_sharded(setattr) -> dict:
    """``rm64.analytic`` as a sharded deployment: the same cell on four
    chips, so the harness serves it with a ``ShardedEngine``."""
    load = harness.Cell.load
    setattr(harness.Cell, "load", staticmethod(
        lambda workload: dataclasses.replace(load(workload), chips=SHARDS)))
    return run_small("rm64.analytic", setattr)


def _upload_devices(stderr: str) -> list[list[str]]:
    """Each resident chunk's devices, from the harness's upload line."""
    prefix = "upload: shard rows on devices "
    (line,) = [x for x in stderr.splitlines() if x.startswith(prefix)]
    return [devs for _, devs in json.loads(line[len(prefix):])]


def test_sharded_config_runs_correct_on_cpu():
    """Four shards over a four-device CPU mesh, in a child process: the
    answers are correct, every read answers, each shard's rows sit on a
    chip of their own, and every chip's peak is reported."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={SHARDS}"))
    env["PYTHONPATH"] = os.pathsep.join(
        [str(harness.ROOT), str(harness.ROOT / "src")])
    out = subprocess.run(
        [sys.executable, "-c", "import json; from bench import "
         "test_bench_cells as t; print(json.dumps(t.run_sharded(setattr)))"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 8
    assert set(r["metrics"]) == {"read_qps", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == SHARDS
    per_chip = r["device"]["memory_peak_bytes_per_chip"]
    assert len(per_chip) == SHARDS
    assert r["device"]["memory_peak_bytes"] == max(per_chip)
    placed = _upload_devices(out.stderr)
    assert len(placed) == SHARDS and all(len(d) == 1 for d in placed)
    assert len({d[0] for d in placed}) == SHARDS


def test_sharded_cell_needs_its_chips(monkeypatch):
    """A four-chip cell refuses to start on this process's one device, even
    where the look for a TPU is skipped: it never shards onto fewer."""
    with pytest.raises(harness.NoAccelerator, match="needs 4"):
        run_sharded(monkeypatch.setattr)
