"""The RME projection engine as Pallas TPU kernels — BSL / PCK / MLP revisions.

Paper §5.2 evaluates three hardware revisions of the engine; we reproduce each
as a structurally faithful Pallas variant (see DESIGN.md §2 for the mapping):

* ``BSL`` — baseline: one Fetch-Unit transaction at a time, each extracted
  column chunk written straight to the Reorganization Buffer.  Pallas grid is
  ``(row_blocks, Q)``: one enabled column copied per grid step, stored directly
  into its slice of the output block (many small stores; the output block is
  revisited Q times).
* ``PCK`` — packer register: column chunks accumulate in a register until a
  full cache line is assembled, then a single BRAM write.  Pallas: a VMEM
  scratch accumulator collects all Q column slices; the packed block is written
  to the output once, on the last column step.
* ``MLP`` — memory-level parallelism (16 outstanding transactions).  Pallas:
  whole-row tiles stream through the automatically double-buffered pipeline
  (outstanding DMAs), and all Q columns are sliced and packed in one vectorized
  step.  This is the TPU-native formulation and the production default, exactly
  as MLP is the paper's production revision.

Tables are int32 word buffers ``(N, row_words)``; geometry is static (the
configuration port is written once per query, paper Table 1), so each distinct
geometry traces its own kernel — matching "the RME is runtime-configurable and
hence usable for multiple queries" at the cost of one trace per geometry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schema import TableGeometry

from .common import DEFAULT_BLOCK_ROWS, column_slices as _column_slices
from .common import pad_rows as _pad_rows
from .common import resolve_interpret

__all__ = [
    "DEFAULT_BLOCK_ROWS", "project", "project_xla", "vmem_footprint_bytes",
]


# --------------------------------------------------------------------- MLP
def _mlp_kernel(slices, x_ref, o_ref):
    parts = [x_ref[:, src : src + w] for src, _, w in slices]
    o_ref[...] = jnp.concatenate(parts, axis=1)


# --------------------------------------------------------------------- PCK
def _pck_kernel(slices, q, x_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    for jj, (src, dst, w) in enumerate(slices):
        @pl.when(j == jj)
        def _copy(src=src, dst=dst, w=w):
            # the packer register accumulates one column chunk per transaction
            acc_ref[:, dst : dst + w] = x_ref[:, src : src + w]

    @pl.when(j == q - 1)
    def _flush():
        # single write of the fully packed line to the reorganization buffer
        o_ref[...] = acc_ref[...]


# --------------------------------------------------------------------- BSL
def _bsl_kernel(slices, x_ref, o_ref):
    j = pl.program_id(1)
    for jj, (src, dst, w) in enumerate(slices):
        @pl.when(j == jj)
        def _copy(src=src, dst=dst, w=w):
            # no packer: every extracted chunk is its own buffer write
            o_ref[:, dst : dst + w] = x_ref[:, src : src + w]


@functools.partial(
    jax.jit, static_argnames=("geom", "revision", "block_rows", "interpret")
)
def project(
    words: jax.Array,
    geom: TableGeometry,
    revision: str = "mlp",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed projection ``(N, row_words) -> (N, out_words)`` via the RME.

    ``interpret=None`` picks from the backend: the Pallas interpreter off
    the TPU (validation), Mosaic-compiled BlockSpecs driving HBM→VMEM DMA on
    it.  ``words.shape[1]`` may exceed ``geom.row_words`` (hidden MVCC words
    ride along in storage but are never shipped unless enabled).
    """
    n, row_words = words.shape
    if row_words < geom.row_words:
        raise ValueError(f"storage rows {row_words}w < geometry rows {geom.row_words}w")
    out_w = geom.out_words_per_row
    slices = _column_slices(geom)
    x = _pad_rows(words, block_rows)
    n_pad = x.shape[0]
    grid_rows = n_pad // block_rows

    in_spec_row = pl.BlockSpec((block_rows, row_words), lambda i, *_: (i, 0))
    out_shape = jax.ShapeDtypeStruct((n_pad, out_w), jnp.int32)

    if revision == "mlp":
        out = pl.pallas_call(
            functools.partial(_mlp_kernel, slices),
            grid=(grid_rows,),
            in_specs=[pl.BlockSpec((block_rows, row_words), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, out_w), lambda i: (i, 0)),
            out_shape=out_shape,
            interpret=resolve_interpret(interpret),
        )(x)
    elif revision == "pck":
        out = pl.pallas_call(
            functools.partial(_pck_kernel, slices, geom.q),
            grid=(grid_rows, geom.q),
            in_specs=[in_spec_row],
            out_specs=pl.BlockSpec((block_rows, out_w), lambda i, j: (i, 0)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((block_rows, out_w), jnp.int32)],
            interpret=resolve_interpret(interpret),
        )(x)
    elif revision == "bsl":
        out = pl.pallas_call(
            functools.partial(_bsl_kernel, slices),
            grid=(grid_rows, geom.q),
            in_specs=[in_spec_row],
            out_specs=pl.BlockSpec((block_rows, out_w), lambda i, j: (i, 0)),
            out_shape=out_shape,
            interpret=resolve_interpret(interpret),
        )(x)
    else:
        raise ValueError(f"unknown RME revision {revision!r}")
    return out[:n]


@functools.partial(jax.jit, static_argnames=("geom",))
def project_xla(words: jax.Array, geom: TableGeometry) -> jax.Array:
    """Production XLA path (fused gather); semantically identical to the kernels.

    Used where the program is lowered for CPU/dry-run (Pallas TPU kernels are
    swapped in on real hardware by `repro.core.engine` revision selection).
    """
    idx = []
    for off, w in zip(geom.col_word_offsets, geom.col_word_widths):
        idx.extend(range(off, off + w))
    return jnp.take(words, jnp.asarray(idx, dtype=jnp.int32), axis=1)


def vmem_footprint_bytes(
    geom: TableGeometry, block_rows: int = DEFAULT_BLOCK_ROWS, revision: str = "mlp"
) -> int:
    """Modeled VMEM working set of one grid step (the 'data SPM' budget).

    MLP double-buffers the row tile (Pallas pipeline) and holds the packed
    output block; PCK adds the packer scratch; BSL holds a row tile + output.
    """
    row_tile = block_rows * geom.row_words * 4
    out_tile = block_rows * geom.out_words_per_row * 4
    if revision == "mlp":
        return 2 * row_tile + 2 * out_tile  # double-buffered in and out
    if revision == "pck":
        return row_tile + 2 * out_tile
    return row_tile + out_tile
