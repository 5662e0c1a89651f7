"""Fused near-memory selection + aggregation kernels (paper Q0/Q3 offload).

The paper's RME prototype offloads projection and "lays the groundwork for
pushing more functionality, i.e., selection, aggregation, group by" (§1, §8).
We implement that next step: the Pallas grid step reads a row tile, extracts
only the predicate and aggregate words, applies the predicate, and accumulates a
partial sum — nothing but a scalar ever leaves the engine.  This is the
beyond-paper extension of the reproduction (recorded in EXPERIMENTS.md §Perf).

MVCC snapshots ride along: when the storage rows carry the two hidden timestamp
words, the kernels take the snapshot time as a scalar operand and fuse the
row-validity test into the predicate, exactly as paper §4 describes the RME
generating only the rows valid at query time.  Padded rows are invalid by
construction (ts_begin = TS_INF).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import DEFAULT_BLOCK_ROWS, group_ids, resolve_interpret, tile_row_ids
from .common import decode as _decode
from .common import pred_mask as _pred


def _agg_kernel(
    spec: tuple,
    x_ref,  # (block_rows, row_words) int32 row tile
    k_ref,  # (1, 1) predicate constant (bits of int32/float32)
    ts_ref,  # (1, 1) snapshot time (int32); ignored unless ts_word >= 0
    o_ref,  # (1, 2) float32: [sum, count]
):
    agg_word, agg_dtype, pred_word, pred_dtype, pred_op, ts_word, n_rows = spec
    i = pl.program_id(0)
    block_rows = x_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    vals = _decode(x_ref[:, agg_word], agg_dtype).astype(jnp.float32)
    k = _decode(k_ref[0, 0], pred_dtype)
    mask = _pred(_decode(x_ref[:, pred_word], pred_dtype), pred_op, k)
    # padded tail rows (beyond the true row count) never contribute
    ridx = tile_row_ids(i, block_rows)
    mask = mask & (ridx < n_rows)
    if ts_word >= 0:
        ts = ts_ref[0, 0]
        begin = x_ref[:, ts_word]
        end = x_ref[:, ts_word + 1]
        mask = mask & (begin <= ts) & (ts < end)
    fm = mask.astype(jnp.float32)
    # a (1, 2) vector store: Mosaic cannot store scalars to VMEM
    contrib = jnp.stack([vals * fm, fm], axis=1)  # (B, 2)
    o_ref[...] += jnp.sum(contrib, axis=0, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=(
        "agg_word",
        "agg_dtype",
        "pred_word",
        "pred_dtype",
        "pred_op",
        "ts_word",
        "block_rows",
        "interpret",
    ),
)
def aggregate(
    words: jax.Array,
    agg_word: int,
    agg_dtype: str = "int32",
    pred_word: int = 0,
    pred_dtype: str = "int32",
    pred_op: str = "none",
    pred_k=0,
    ts: int = 0,
    ts_word: int = -1,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """``SELECT SUM(a), COUNT(*) FROM t WHERE pred(b)`` fused in the engine.

    Returns float32 ``[sum, count]``.  ``ts_word >= 0`` enables the fused MVCC
    snapshot test against storage words ``ts_word`` / ``ts_word + 1``.
    """
    n, row_words = words.shape
    pad = (-n) % block_rows
    if pad:
        words = jnp.concatenate(
            [words, jnp.zeros((pad, row_words), dtype=jnp.int32)], axis=0
        )
    n_pad = words.shape[0]

    k_arr = jnp.asarray(pred_k, dtype=jnp.float32 if pred_dtype == "float32" else jnp.int32)
    k_bits = jax.lax.bitcast_convert_type(k_arr, jnp.int32).reshape(1, 1)
    ts_arr = jnp.asarray(ts, dtype=jnp.int32).reshape(1, 1)
    spec = (agg_word, agg_dtype, pred_word, pred_dtype, pred_op, ts_word, n)

    out = pl.pallas_call(
        functools.partial(_agg_kernel, spec),
        grid=(n_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, row_words), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 2), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(words, k_bits, ts_arr)
    return out[0]


def _groupby_kernel(
    spec: tuple,
    x_ref,  # (block_rows, row_words)
    k_ref,  # (1, 1)
    ts_ref,  # (1, 1)
    o_ref,  # (num_groups, 2) float32: [:, 0]=sum, [:, 1]=count
):
    (group_word, agg_word, agg_dtype, pred_word, pred_dtype, pred_op, ts_word,
     num_groups, n_rows) = spec
    i = pl.program_id(0)
    block_rows = x_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = group_ids(x_ref[:, group_word], num_groups)  # (B,)
    vals = _decode(x_ref[:, agg_word], agg_dtype).astype(jnp.float32)
    k = _decode(k_ref[0, 0], pred_dtype)
    mask = _pred(_decode(x_ref[:, pred_word], pred_dtype), pred_op, k)
    ridx = tile_row_ids(i, block_rows)
    mask = mask & (ridx < n_rows)
    if ts_word >= 0:
        ts = ts_ref[0, 0]
        mask = mask & (x_ref[:, ts_word] <= ts) & (ts < x_ref[:, ts_word + 1])
    fm = mask.astype(jnp.float32)
    # One-hot × matmul: group-by as an MXU contraction (TPU-native group-by).
    onehot = (g[:, None] == jax.lax.iota(jnp.int32, num_groups)[None, :]).astype(
        jnp.float32
    )  # (B, G)
    contrib = jnp.stack([vals * fm, fm], axis=1)  # (B, 2)
    o_ref[...] += jax.lax.dot_general(
        onehot, contrib, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # (G, 2)


@functools.partial(
    jax.jit,
    static_argnames=(
        "group_word",
        "agg_word",
        "agg_dtype",
        "num_groups",
        "pred_word",
        "pred_dtype",
        "pred_op",
        "ts_word",
        "block_rows",
        "interpret",
    ),
)
def groupby_sum(
    words: jax.Array,
    group_word: int,
    agg_word: int,
    num_groups: int,
    agg_dtype: str = "int32",
    pred_word: int = 0,
    pred_dtype: str = "int32",
    pred_op: str = "none",
    pred_k=0,
    ts: int = 0,
    ts_word: int = -1,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``SELECT SUM(a), COUNT(*) ... GROUP BY g`` via one-hot MXU contraction.

    Returns ``(sums[G], counts[G])``.  The group key domain is ``g mod G``
    (static G — the hardware analogue of a fixed number of accumulators).
    """
    n, row_words = words.shape
    pad = (-n) % block_rows
    if pad:
        words = jnp.concatenate(
            [words, jnp.zeros((pad, row_words), dtype=jnp.int32)], axis=0
        )
    n_pad = words.shape[0]

    k_arr = jnp.asarray(pred_k, dtype=jnp.float32 if pred_dtype == "float32" else jnp.int32)
    k_bits = jax.lax.bitcast_convert_type(k_arr, jnp.int32).reshape(1, 1)
    ts_arr = jnp.asarray(ts, dtype=jnp.int32).reshape(1, 1)
    spec = (
        group_word, agg_word, agg_dtype, pred_word, pred_dtype, pred_op, ts_word,
        num_groups, n,
    )
    out = pl.pallas_call(
        functools.partial(_groupby_kernel, spec),
        grid=(n_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, row_words), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((num_groups, 2), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_groups, 2), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(words, k_bits, ts_arr)
    return out[:, 0], out[:, 1]
