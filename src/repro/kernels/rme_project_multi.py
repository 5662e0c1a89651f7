"""Scan-sharing multi-view projection — one row-store pass, many packed outputs.

The paper's RME serves several ephemeral views from one Fetch-Unit stream: the
Requestor walks the row store once and each enabled column chunk is routed to
its view's slice of the Reorganization Buffer.  Per-view kernels lose exactly
that amortization — a batch of Q0–Q5 views over one table re-reads the base
data once per view.  This module restores it in software: the Pallas grid
streams each row tile through VMEM **once** and emits every registered column
group's packed block from that single pass.

Only the MLP formulation applies here (whole-row tiles through the
double-buffered pipeline, all views packed per grid step); the BSL/PCK
micro-architecture studies are per-view by construction, so the engine routes
their batched materializations through this kernel too.  ``project_multi_xla``
is the fused-gather fallback used when lowering for non-TPU targets: a single
gather of the *union* of enabled words, then per-view slicing out of that one
pass.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.schema import TableGeometry

from .common import DEFAULT_BLOCK_ROWS
from .common import column_slices as _column_slices
from .common import pad_rows as _pad_rows
from .common import resolve_interpret


def _mlp_multi_kernel(view_slices, x_ref, *o_refs):
    # one VMEM row tile feeds every view's packed output block
    for slices, o_ref in zip(view_slices, o_refs):
        parts = [x_ref[:, src : src + w] for src, _, w in slices]
        o_ref[...] = jnp.concatenate(parts, axis=1)


def _check_geoms(row_words: int, geoms: Sequence[TableGeometry]) -> None:
    if not geoms:
        raise ValueError("project_multi needs at least one geometry")
    for g in geoms:
        if row_words < g.row_words:
            raise ValueError(
                f"storage rows {row_words}w < geometry rows {g.row_words}w"
            )


@functools.partial(
    jax.jit, static_argnames=("geoms", "revision", "block_rows", "interpret")
)
def project_multi(
    words: jax.Array,
    geoms: tuple[TableGeometry, ...],
    revision: str = "mlp",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, ...]:
    """Shared-scan projection ``(N, row_words) -> [(N, out_words_v), ...]``.

    All geometries must describe views over the same row layout; the row store
    is streamed exactly once regardless of how many views are materialized.
    ``revision="xla"`` dispatches the fused-gather fallback; every Pallas
    revision shares the MLP streaming formulation (see module docstring).
    """
    if revision == "xla":
        return project_multi_xla(words, geoms)
    n, row_words = words.shape
    _check_geoms(row_words, geoms)
    x = _pad_rows(words, block_rows)
    n_pad = x.shape[0]
    grid_rows = n_pad // block_rows

    outs = pl.pallas_call(
        functools.partial(
            _mlp_multi_kernel, tuple(_column_slices(g) for g in geoms)
        ),
        grid=(grid_rows,),
        in_specs=[pl.BlockSpec((block_rows, row_words), lambda i: (i, 0))],
        out_specs=tuple(
            pl.BlockSpec((block_rows, g.out_words_per_row), lambda i: (i, 0))
            for g in geoms
        ),
        out_shape=tuple(
            jax.ShapeDtypeStruct((n_pad, g.out_words_per_row), jnp.int32)
            for g in geoms
        ),
        interpret=resolve_interpret(interpret),
    )(x)
    return tuple(o[:n] for o in outs)


@functools.partial(jax.jit, static_argnames=("geoms",))
def project_multi_xla(
    words: jax.Array, geoms: tuple[TableGeometry, ...]
) -> tuple[jax.Array, ...]:
    """Fused-gather fallback: gather the union of enabled words once, slice per view."""
    _check_geoms(words.shape[1], geoms)
    union: list[int] = []
    seen: set[int] = set()
    for g in geoms:
        for off, w in zip(g.col_word_offsets, g.col_word_widths):
            for word in range(off, off + w):
                if word not in seen:
                    seen.add(word)
                    union.append(word)
    union.sort()
    pos = {word: i for i, word in enumerate(union)}
    shared = jnp.take(words, jnp.asarray(union, dtype=jnp.int32), axis=1)
    outs = []
    for g in geoms:
        idx = []
        for off, w in zip(g.col_word_offsets, g.col_word_widths):
            idx.extend(pos[word] for word in range(off, off + w))
        outs.append(jnp.take(shared, jnp.asarray(idx, dtype=jnp.int32), axis=1))
    return tuple(outs)
