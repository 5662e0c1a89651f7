"""Plain numpy reference for the benchmark's tables and queries.

Independent of ``src/repro``: it imports nothing of the engine and reads only
the columns the benchmark generated from ``--seed``.  The data generator and
the join oracle are copied from ``chip_smoke.py`` (``make_columns``,
``make_build_columns``, ``Oracle``) and extended to any column count, to the
mix's column choices and to the group-by average over ``groups`` groups.

Answers have the shapes the served path returns for an unpinned read:

* ``sum``: the float sum of the aggregated column over matching rows;
* ``project``: the ``(rows, k)`` int32 block of the columns, in schema order;
* ``filter``: ``(packed, mask)``, rows failing the predicate zeroed;
* ``groupby_avg``: the per-group averages, ``avg = sum / max(count, 1)``;
* ``join``: ``(s_proj, r_proj, matched)`` per probe row, ``r_proj`` zero
  where no build key matches.
"""

from __future__ import annotations

import numpy as np

# the paper's synthetic relation: 4-byte ints uniform in [-1000, 1000)
VALUE_LOW, VALUE_HIGH = -1000, 1000


def column_names(n: int) -> list[str]:
    return [f"A{i + 1}" for i in range(n)]


def make_columns(rng: np.random.Generator, rows: int, ncols: int) -> dict:
    """``ncols`` int32 columns ``A1..`` uniform in [-1000, 1000)."""
    block = rng.integers(VALUE_LOW, VALUE_HIGH, (ncols, rows), dtype=np.int32)
    return dict(zip(column_names(ncols), block))


def make_build_columns(rng: np.random.Generator, rows: int, ncols: int,
                       key: str) -> dict:
    """The build relation: as ``make_columns``, with unique even keys in
    ``key``, so about half of the probe keys (uniform ints) find a match."""
    cols = make_columns(rng, rows, ncols)
    cols[key] = rng.permutation(np.arange(-rows, rows, 2, dtype=np.int32))
    return cols


def _pred_mask(col: np.ndarray, op: str, k: int) -> np.ndarray:
    if op == "gt":
        return col > k
    if op == "lt":
        return col < k
    raise ValueError(f"unknown predicate {op!r}")


class Reference:
    """Expected answers over the probe table ``cols`` and build table
    ``build`` (joined on ``key``)."""

    def __init__(self, cols: dict, build: dict, key: str):
        self.cols = cols
        self.order = {name: i for i, name in enumerate(cols)}
        self._build = build
        order = np.argsort(build[key], kind="stable")
        self._build_keys = build[key][order]
        self._build_order = order

    def _mask(self, tpl: dict, k: int | None) -> np.ndarray | None:
        if "pred" not in tpl:
            return None
        col, op = tpl["pred"]
        return _pred_mask(self.cols[col], op, k)

    def _block(self, columns) -> np.ndarray:
        names = sorted(columns, key=self.order.__getitem__)
        return np.stack([self.cols[c] for c in names], axis=1)

    def answer(self, tpl: dict, k: int | None):
        """The expected result of one read of template ``tpl`` with
        predicate constant ``k``, plus the scale each aggregate's error is
        measured against (``None`` for row outputs)."""
        kind = tpl["kind"]
        mask = self._mask(tpl, k)
        if kind == "project":
            return self._block(tpl["columns"]), None
        if kind == "filter":
            packed = self._block(tpl["columns"])
            return (np.where(mask[:, None], packed, 0), mask), None
        if kind == "sum":
            v = self.cols[tpl["agg"]].astype(np.float64)
            if mask is not None:
                v = v[mask]
            return float(v.sum()), max(float(np.abs(v).sum()), 1.0)
        if kind == "groupby_avg":
            groups = tpl["groups"]
            g = np.mod(self.cols[tpl["group"]].astype(np.int64), groups)
            v = self.cols[tpl["agg"]].astype(np.float64)
            if mask is not None:
                g, v = g[mask], v[mask]
            sums = np.bincount(g, weights=v, minlength=groups)
            abs_sums = np.bincount(g, weights=np.abs(v), minlength=groups)
            counts = np.bincount(g, minlength=groups).astype(np.float64)
            den = np.maximum(counts, 1.0)
            # error of an average is measured against the mean magnitude
            return sums / den, np.maximum(abs_sums / den, 1.0)
        if kind == "join":
            keys = self.cols[tpl["key"]]
            at = np.minimum(np.searchsorted(self._build_keys, keys),
                            len(self._build_keys) - 1)
            matched = self._build_keys[at] == keys
            payload = self._build[tpl["right"]][self._build_order][at]
            return (self.cols[tpl["left"]].copy(),
                    np.where(matched, payload, 0), matched), None
        raise ValueError(f"unknown template kind {kind!r}")
