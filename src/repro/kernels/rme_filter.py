"""Fused selection + projection kernel (Q2-style pushdown).

``SELECT A1 FROM t WHERE A3 > k``: the engine ships only the projected column
group, with rows failing the predicate zeroed and a validity bitmap alongside.
Static-shape TPU adaptation of the paper's future-work selection offload: the
row *positions* are preserved (no compaction — XLA needs static shapes), so the
consumer runs predicated compute on the packed view.  The data-movement win is
identical to the paper's: non-projected columns never leave the engine.
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


def _filter_kernel(spec, x_ref, k_ref, ts_ref, o_ref, m_ref):
    slices, pred_word, pred_dtype, pred_op, ts_word, n_rows = spec
    i = pl.program_id(0)
    block_rows = x_ref.shape[0]

    k = _decode(k_ref[0, 0], pred_dtype)
    mask = _pred(_decode(x_ref[:, pred_word], pred_dtype), pred_op, k)
    ridx = tile_row_ids(i, block_rows)
    mask = mask & (ridx < n_rows)
    if ts_word >= 0:
        ts = ts_ref[0, 0]
        mask = mask & (x_ref[:, ts_word] <= ts) & (ts < x_ref[:, ts_word + 1])

    parts = [x_ref[:, src : src + w] for src, _, w in slices]
    packed = jnp.concatenate(parts, axis=1)
    o_ref[...] = jnp.where(mask[:, None], packed, 0)
    m_ref[...] = mask[:, None].astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "geom", "pred_word", "pred_dtype", "pred_op", "ts_word", "block_rows",
        "interpret",
    ),
)
def filter_project(
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
    """Returns ``(packed (N, out_words) int32, mask (N,) bool)``."""
    n, row_words = words.shape
    pad = (-n) % block_rows
    if pad:
        words = jnp.concatenate(
            [words, jnp.zeros((pad, row_words), dtype=jnp.int32)], axis=0
        )
    n_pad = words.shape[0]
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

    packed, mask = pl.pallas_call(
        functools.partial(_filter_kernel, spec),
        grid=(n_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, row_words), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, out_w), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, out_w), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(words, k_bits, ts_arr)
    return packed[:n], mask[:n, 0].astype(bool)
