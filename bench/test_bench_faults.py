"""The correctness check must fail what it is there to catch.

* The control (the reference computed in bfloat16, one precision below what
  the configurations state) comes out not correct.
* A run whose timed path alters an answer where the kernel produces it comes
  out not correct: once for a row output, once for an aggregate.

All on the CPU at a small size, the run's look for a chip skipped."""

from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from bench import check, control, harness

ROWS = 4096
SEED = 2**31 + 99


@pytest.mark.parametrize("workload", ["rm64.analytic", "rm256.analytic"])
def test_control_is_not_correct(workload):
    cell = harness.Cell.load(workload)
    for seed in (SEED, SEED + 1, SEED + 2):
        numbers = control.readings(cell.config, cell.mix, seed, rows=ROWS)
        assert not check.verdict(numbers), numbers


def _alter(kind):
    """Wrap the fused kernel's result regrouping so that every output of
    one request kind comes back altered."""
    import repro.core  # noqa: F401  (imports the kernels in their order)
    from repro.kernels import rme_scan_multi as KR

    orig = KR._unflatten

    def altered(requests, flat, n):
        out = orig(requests, flat, n)
        for i, req in enumerate(requests):
            if kind == "rows" and isinstance(req, KR.ProjectRequest):
                out[i] = out[i] + 1
            if kind == "aggregate" and isinstance(req, KR.AggregateRequest):
                out[i] = out[i] * jnp.asarray([1.01, 1.0]) + jnp.asarray(
                    [1000.0, 0.0])
        return out

    return KR, altered


@pytest.mark.parametrize("kind", ["rows", "aggregate"])
def test_altered_answer_is_not_correct(kind, monkeypatch):
    KR, altered = _alter(kind)
    monkeypatch.setattr(KR, "_unflatten", altered)
    monkeypatch.setattr(harness, "configure_compile_cache", lambda: None)
    r = harness.run("rm64.analytic", SEED, 2.0, False,
                    t0=time.perf_counter(), rows=ROWS,
                    require_accelerator=False)
    assert not r["correct"]
    failed = {k for k, c in r["checks"].items() if c["value"] > c["limit"]}
    assert failed == ({"row_mismatch"} if kind == "rows" else {"agg_err"})
