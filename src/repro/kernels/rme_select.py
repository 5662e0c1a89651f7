"""Near-memory selection WITH compaction — the paper's §8 selection offload.

``filter_project`` (rme_filter.py) preserves row positions and ships a
validity mask: simple, but failing rows still occupy bus width.  This kernel
goes the final step the paper sketches for the hardware: rows that fail the
predicate are *compacted out* inside the engine, so the bytes shipped to the
consumer scale with selectivity, not cardinality.

TPU adaptation of a data-dependent output size (XLA needs static shapes):
each block emits a dense prefix of its selected rows plus a per-block count
— the same contract a DMA engine with a fill-level register provides.  The
host-side wrapper optionally concatenates the prefixes into one dense
relation (cheap: one gather over block offsets).

Compaction inside the kernel is expressed as a *sort by (!keep)* — a stable
sort moves selected rows to the front of the block while preserving order,
mapping onto the TPU's vectorized sort rather than serial control flow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.schema import TableGeometry

from .common import DEFAULT_BLOCK_ROWS, resolve_interpret, tile_row_ids
from .common import decode as _decode
from .common import pred_mask as _pred


def _select_kernel(spec, x_ref, k_ref, ts_ref, o_ref, c_ref):
    slices, pred_word, pred_dtype, pred_op, ts_word, n_rows = spec
    i = pl.program_id(0)
    block_rows = x_ref.shape[0]

    k = _decode(k_ref[0, 0], pred_dtype)
    keep = _pred(_decode(x_ref[:, pred_word], pred_dtype), pred_op, k)
    ridx = tile_row_ids(i, block_rows)
    keep = keep & (ridx < n_rows)
    if ts_word >= 0:
        ts = ts_ref[0, 0]
        keep = keep & (x_ref[:, ts_word] <= ts) & (ts < x_ref[:, ts_word + 1])

    parts = [x_ref[:, src : src + w] for src, _, w in slices]
    packed = jnp.concatenate(parts, axis=1)  # (B, out_w)
    # stable compaction: selected rows first, original order preserved
    order = jnp.argsort(jnp.logical_not(keep), stable=True)
    compacted = jnp.take(packed, order, axis=0)
    count = jnp.sum(keep.astype(jnp.int32))
    valid = jax.lax.iota(jnp.int32, block_rows) < count
    o_ref[...] = jnp.where(valid[:, None], compacted, 0)
    c_ref[0, 0] = count


@functools.partial(
    jax.jit,
    static_argnames=(
        "geom", "pred_word", "pred_dtype", "pred_op", "ts_word", "block_rows",
        "interpret",
    ),
)
def select_compact(
    words: jax.Array,
    geom: TableGeometry,
    pred_word: int,
    pred_dtype: str = "int32",
    pred_op: str = "gt",
    pred_k=0,
    ts: int = 0,
    ts_word: int = -1,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns ``(blocks (n_blocks, block_rows, out_w), counts (n_blocks,))``.

    ``blocks[b, :counts[b]]`` are the packed projections of the selected
    rows of block ``b`` in original order; the tail is zero-filled.
    """
    n, row_words = words.shape
    pad = (-n) % block_rows
    if pad:
        words = jnp.concatenate(
            [words, jnp.zeros((pad, row_words), jnp.int32)], axis=0
        )
    n_pad = words.shape[0]
    grid = n_pad // block_rows
    out_w = geom.out_words_per_row
    slices = tuple(
        zip(geom.col_word_offsets, geom.out_word_offsets, geom.col_word_widths)
    )
    k_arr = jnp.asarray(
        pred_k, dtype=jnp.float32 if pred_dtype == "float32" else jnp.int32
    )
    k_bits = jax.lax.bitcast_convert_type(k_arr, jnp.int32).reshape(1, 1)
    ts_arr = jnp.asarray(ts, dtype=jnp.int32).reshape(1, 1)
    spec = (slices, pred_word, pred_dtype, pred_op, ts_word, n)

    blocks, counts = pl.pallas_call(
        functools.partial(_select_kernel, spec),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_rows, row_words), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, out_w), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, out_w), jnp.int32),
            jax.ShapeDtypeStruct((grid, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(words, k_bits, ts_arr)
    return blocks.reshape(grid, block_rows, out_w), counts[:, 0]


def densify(blocks: jax.Array, counts: jax.Array, total: int) -> jax.Array:
    """Concatenate block prefixes into one dense (total, out_w) relation.

    ``total`` is a static bound (≥ counts.sum()); surplus rows are zero.
    One gather over global positions — the host-side Reorganization Buffer
    read-out.
    """
    grid, block_rows, out_w = blocks.shape
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    # global destination of each (block, slot); invalid slots -> `total`
    slot = jnp.arange(block_rows, dtype=jnp.int32)
    dest = starts[:, None] + slot[None, :]
    valid = slot[None, :] < counts[:, None]
    dest = jnp.where(valid, dest, total)
    flat = blocks.reshape(grid * block_rows, out_w)
    out = jnp.zeros((total + 1, out_w), jnp.int32).at[dest.reshape(-1)].set(
        flat, mode="drop"
    )
    return out[:total]


def select_compact_ref(
    words: jax.Array, geom: TableGeometry, pred_word: int,
    pred_dtype: str = "int32", pred_op: str = "gt", pred_k=0,
) -> jax.Array:
    """Oracle: numpy-style dense selection of packed projections."""
    import numpy as np

    from . import ref as R

    packed = np.asarray(R.project_ref(words[:, : geom.row_words], geom))
    vals = np.asarray(_decode(words[:, pred_word], pred_dtype))
    mask = np.asarray(_pred(jnp.asarray(vals), pred_op, pred_k))
    return packed[mask]
