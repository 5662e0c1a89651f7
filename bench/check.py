"""The comparison that decides ``correct``: sampled answers of the timed path
against the plain reference (``bench/reference.py``).

Numbers compared, each with its limit (``PERF.md`` gives the readings each
limit was set from):

* ``row_mismatch`` — elements of the sampled row outputs (projections, filter
  blocks and masks, join outputs) that differ from the reference, a shape
  mismatch counting every element.  Row outputs are exact: limit 0.
* ``agg_err`` — the largest error of a sampled aggregate (a sum, or a group's
  average) against the float64 reference, as a share of the sum of the
  magnitudes it adds (for an average: of the mean magnitude).  The
  configurations state float32 accumulation.
* ``failed_reads`` — reads of the window that raised instead of answering,
  or never answered: limit 0.
"""

from __future__ import annotations

import dataclasses

import numpy as np

LIMITS = {
    "row_mismatch": 0,
    "agg_err": 1e-6,
    "failed_reads": 0,
}


# the error of an aggregate that came back with the wrong shape or not as a
# finite number (JSON has no infinity)
WRONG = float(np.finfo(np.float64).max)


def host(result):
    """A served answer pulled to numpy: a join's result dataclass becomes
    the tuple of its fields, a scalar stays a float."""
    if dataclasses.is_dataclass(result):
        return tuple(np.asarray(getattr(result, f.name))
                     for f in dataclasses.fields(result))
    if isinstance(result, tuple):
        return tuple(np.asarray(x) for x in result)
    if isinstance(result, float):
        return result
    return np.asarray(result)


def _mismatch(got, want) -> int:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(got.size, want.size, 1)
    return int(np.count_nonzero(got != want))


def compare(samples, ref, failed_reads: int) -> dict:
    """``samples``: ``(read, answer)`` pairs, ``answer`` in numpy form
    (:func:`host`); ``failed_reads`` counts the window's reads that raised
    or never answered (a failed sampled read is among them)."""
    rows, err = 0, 0.0
    for read, got in samples:
        if got is None:
            continue
        want, scale = ref.answer(read.tpl, read.k)
        if scale is None:
            parts_got = got if isinstance(got, tuple) else (got,)
            parts_want = want if isinstance(want, tuple) else (want,)
            if len(parts_got) != len(parts_want):
                rows += sum(np.asarray(w).size for w in parts_want)
                continue
            rows += sum(_mismatch(g, w) for g, w in zip(parts_got, parts_want))
        else:
            g = np.asarray(got, np.float64)
            if g.shape != np.shape(want) or not np.all(np.isfinite(g)):
                err = WRONG  # no number: as wrong as a number can be
                continue
            err = max(err, float(np.max(np.abs(g - want) / scale)))
    return {"row_mismatch": rows, "agg_err": err,
            "failed_reads": failed_reads}


def verdict(numbers: dict) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def report(numbers: dict) -> dict:
    """Each number beside its limit, as the result line carries them."""
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
