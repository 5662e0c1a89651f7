"""Benchmark driver: one module per paper table/figure + the LM step bench.

Prints ``name,us_per_call,derived`` CSV rows (and a trailing summary), the
format consumed by EXPERIMENTS.md.  ``python -m benchmarks.run [pattern]``
runs the subset whose module name contains ``pattern``;
``python -m benchmarks.run --smoke`` runs every figure at smoke scale (tiny
tables, single iterations) — the CI job that catches kernel-lowering
regressions without paying for real measurements.

``--json PATH`` additionally writes the results machine-readably: every row's
name, wall time, and parsed ``derived`` key=value fields (bytes moved,
throughput, latency percentiles, ...), so perf can be diffed across PRs
(``benchmarks/run.py --json BENCH_pr3.json`` then compare files).

``--update-baselines`` refreshes the committed perf-gate baseline
(``benchmarks/baselines/smoke.json`` for ``--smoke``, ``full.json``
otherwise) — run it after an intentional perf change, commit the diff, and
the CI ``perf-gate`` job compares every future run against it
(``python -m benchmarks.perf_gate``).  ``--rows N`` caps every figure's
table size without smoke-mode shortcuts (the nightly job's 50k regime).
"""

import argparse
import json
import os
import pathlib
import time

import jax

from . import (
    fig6_offset_revisions,
    fig7_q1_colwidth,
    fig9_projectivity,
    fig10_queries_colsize,
    fig11_queries_rowsize,
    fig12_join,
    fig13_scaling,
    fig_compression,
    fig_concurrent_queries,
    fig_dist_scaling,
    fig_fault_recovery,
    fig_htap_ingest,
    fig_mixed_batch,
    fig_optimizer,
    fig_scan_sharing,
    fig_selectivity,
    fig_serving_pipeline,
    table2_vmem_budget,
    lm_step,
)
from .common import flush_rows, set_row_cap, set_smoke

BASELINE_DIR = pathlib.Path(__file__).parent / "baselines"

MODULES = [
    fig6_offset_revisions,
    fig7_q1_colwidth,
    fig9_projectivity,
    fig10_queries_colsize,
    fig11_queries_rowsize,
    fig12_join,
    fig13_scaling,
    fig_compression,
    fig_concurrent_queries,
    fig_dist_scaling,
    fig_fault_recovery,
    fig_htap_ingest,
    fig_mixed_batch,
    fig_optimizer,
    fig_scan_sharing,
    fig_selectivity,
    fig_serving_pipeline,
    table2_vmem_budget,
    lm_step,
]


def _parse_derived(derived: str) -> dict:
    """``k1=v1,k2=v2`` -> dict with numbers decoded (non-kv text kept raw)."""
    out: dict = {}
    for part in derived.split(","):
        if "=" not in part:
            if part:
                out.setdefault("notes", []).append(part)
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v.rstrip("x"))
            except ValueError:
                out[k] = v
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("pattern", nargs="?", default="",
                    help="run only modules whose name contains this substring")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny row counts + single iterations (CI regression probe)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write results as JSON for cross-PR perf diffing")
    ap.add_argument("--rows", type=int, default=None, metavar="N",
                    help="cap every figure's table size (nightly: 50000)")
    ap.add_argument("--update-baselines", action="store_true",
                    help="write the report to benchmarks/baselines/ — the "
                         "committed reference the CI perf-gate compares against")
    args = ap.parse_args()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed in-checkout path, so a later process hits what this one
        # compiled; JAX_COMPILATION_CACHE_DIR, when set, wins
        jax.config.update("jax_compilation_cache_dir",
                          str(BASELINE_DIR.parent.parent / ".jax_cache"))
    if args.smoke:
        set_smoke(True)
    if args.rows is not None:
        set_row_cap(args.rows)
    print("name,us_per_call,derived")
    t0 = time.time()
    rows = []
    for mod in MODULES:
        if args.pattern and args.pattern not in mod.__name__:
            continue
        mod.run()
        rows.extend(flush_rows())
    elapsed = time.time() - t0
    print(f"# {len(rows)} rows in {elapsed:.1f}s")
    report = {
        "smoke": args.smoke,
        "pattern": args.pattern,
        "elapsed_s": round(elapsed, 3),
        "rows": [
            {"name": name, "us_per_call": us, "derived": _parse_derived(d)}
            for name, us, d in rows
        ],
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {args.json}")
    if args.update_baselines:
        if args.pattern:
            raise SystemExit("--update-baselines needs a full run (no pattern)")
        BASELINE_DIR.mkdir(exist_ok=True)
        path = BASELINE_DIR / ("smoke.json" if args.smoke else "full.json")
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote baseline {path}")


if __name__ == "__main__":
    main()
