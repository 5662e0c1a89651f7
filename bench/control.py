"""The control of the correctness check: the reference put in the program's
place, computed one precision below what the configurations state.

The configurations state float32 aggregates and exact int32 rows; the
control computes in bfloat16 on the device: every column value is rounded
to bfloat16 (exact only up to 256 in magnitude), and sums accumulate in
bfloat16 by pairwise halving.  ``bench/check.py`` must judge its answers
wrong; the limits in ``PERF.md`` are set between its readings and the
program's.

    python3 -m bench.control --workload rm64.analytic --seeds 1 2 3

reads, for each seed, the first round of the cell's mix over the cell's
data at its full size, and prints the compared numbers (one JSON line per
seed).
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, traffic
from bench.reference import Reference, make_build_columns, make_columns


@jax.jit
def _bf16_sum(x):
    """Sum along the last axis, accumulated in bfloat16 by pairwise
    halving (each level rounds to bfloat16)."""
    x = x.astype(jnp.bfloat16)
    n = x.shape[-1]
    size = 1 << max(n - 1, 0).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, size - n)])
    while x.shape[-1] > 1:
        x = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2)).sum(
            axis=-1, dtype=jnp.bfloat16)
    return x[..., 0]


class Control:
    """Answers of the mix's templates in bfloat16, in the served shapes."""

    def __init__(self, cols: dict, build: dict, key: str):
        self.cols = {k: jnp.asarray(v) for k, v in cols.items()}
        self.order = {name: i for i, name in enumerate(cols)}
        self._ref = Reference(cols, build, key)  # only for the join's keys

    def _low(self, name):
        return self.cols[name].astype(jnp.bfloat16)

    def _mask(self, tpl, k):
        if "pred" not in tpl:
            return None
        col, op = tpl["pred"]
        v = self._low(col)
        return v > k if op == "gt" else v < k

    def answer(self, read: traffic.Read):
        tpl, k = read.tpl, read.k
        kind = tpl["kind"]
        mask = self._mask(tpl, k)
        if kind in ("project", "filter"):
            names = sorted(tpl["columns"], key=self.order.__getitem__)
            block = jnp.stack([self._low(c) for c in names],
                              axis=1).astype(jnp.int32)
            if kind == "project":
                return np.asarray(block)
            return (np.asarray(jnp.where(mask[:, None], block, 0)),
                    np.asarray(mask))
        if kind == "sum":
            v = self._low(tpl["agg"])
            if mask is not None:
                v = jnp.where(mask, v, 0)
            return float(_bf16_sum(v))
        if kind == "groupby_avg":
            g = jnp.remainder(self.cols[tpl["group"]], tpl["groups"])
            v = self._low(tpl["agg"])
            avgs = []
            for group in range(tpl["groups"]):
                rows = g == group
                if mask is not None:
                    rows = rows & mask
                total = _bf16_sum(jnp.where(rows, v, 0))
                count = _bf16_sum(rows.astype(jnp.bfloat16))
                avgs.append(total / jnp.maximum(count, jnp.bfloat16(1)))
            return np.asarray(jnp.stack(avgs).astype(jnp.float32))
        if kind == "join":
            (_, r_proj, matched), _ = self._ref.answer(tpl, k)
            s_proj = np.asarray(self._low(tpl["left"]).astype(jnp.int32))
            r_low = np.asarray(jnp.asarray(r_proj).astype(jnp.bfloat16)
                               .astype(jnp.int32))
            return s_proj, r_low, matched
        raise ValueError(f"unknown template kind {kind!r}")


def readings(cfg: dict, mix: traffic.Mix, seed: int,
             rows: int | None = None) -> dict:
    """The compared numbers of the control over the first round of the
    mix, for one seed, at the configuration's size (or ``rows``)."""
    n = cfg["rows"] if rows is None else rows
    bcfg = cfg["build"]
    cols = make_columns(traffic.rng(seed, traffic.STREAM_DATA), n,
                        cfg["columns"])
    bcols = make_build_columns(traffic.rng(seed, traffic.STREAM_BUILD),
                               bcfg["rows"], bcfg["columns"], bcfg["key"])
    ctl = Control(cols, bcols, bcfg["key"])
    samples = [(r, ctl.answer(r)) for r in next(mix.rounds(seed))]
    del ctl
    return check.compare(samples, Reference(cols, bcols, bcfg["key"]), 0)


def main(argv=None) -> int:
    from bench.harness import ROOT, Cell, configure_compile_cache

    sys.path.insert(0, str(ROOT / "src"))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = Cell.load(args.workload)
    configure_compile_cache()
    dev = jax.devices()[0]
    for seed in args.seeds:
        numbers = readings(cell.config, cell.mix, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "platform": dev.platform, "kind": dev.device_kind,
                          "numbers": numbers,
                          "correct": check.verdict(numbers)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
