"""Shared kernel utilities — one definition for the whole RME kernel suite.

The geometry contract every kernel honors
-----------------------------------------
A kernel's input is one table's row store (or one resident *chunk* of it): an
``(N, row_words)`` int32 buffer whose row stride is the **storage** schema —
the user columns back-to-back, followed by the two hidden MVCC timestamp
words ``__ts_begin`` / ``__ts_end`` (``repro.core.table``).  What a kernel
may touch is governed by word offsets into that stride:

* **Enabled words** — the projected column group of a
  :class:`~repro.core.schema.TableGeometry` (word-aligned widths/offsets,
  the configuration-port payload), plus any predicate / aggregate / group
  words a fused request names.  Only these are semantically read; the
  engine's bus-beat accounting charges exactly their Eq. (3) bursts (the
  union over all requests of a shared pass).
* **Hidden timestamp words** — addressed only via ``ts_word`` (>= 0 fuses
  the MVCC snapshot test ``begin <= ts < end`` into the row mask).  They are
  never part of a projected output, which is why cached packed blocks stay
  byte-valid across deletes/updates (the write path patches only these
  words) — and when a request enables them, they join the enabled-word union
  and are charged like any other burst.
* **Rows** are position-local: a kernel never assumes a global row index
  beyond padded-tail masking, so the same request runs unchanged over a
  whole table or any chunk of it, and per-chunk outputs concatenate (blocked)
  or add (accumulated) — the contract ``scan_multi_chunked`` builds on.

Every fused kernel also shares the conventions below: a default row-tile
height, zero-padding to a whole number of tiles, word-granule column slices,
4-byte column decoding (int32 passthrough / float32 bitcast), and the single
fused predicate (``gt`` / ``lt`` / ``none``).  These used to be copied per
kernel module (``rme_project`` / ``rme_filter`` / ``rme_aggregate``); they
live here once, and the heterogeneous one-pass kernel (``rme_scan_multi``)
composes them the same way the single-op kernels do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.schema import TableGeometry

DEFAULT_BLOCK_ROWS = 256

# VMEM of one TensorCore per TPU ``device_kind``, as the TPU compiler
# reports it for a v5e (128 MiB).  A kind missing here is an error, never a
# default: kernels that size their working set against VMEM must not guess.
VMEM_BYTES_BY_KIND = {
    "TPU v5 lite": 128 << 20,
}

# scoped-VMEM limit the kernels that opt in (the join probe) compile under:
# Mosaic's default is 16 MiB, too small for the resident bucket arrays, so
# the probe asks for this share of the core's VMEM explicitly
VMEM_LIMIT_FRACTION = 0.75


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place ``interpret`` is decided: ``None`` means "from the
    backend" — Mosaic-compiled kernels on a TPU, the Pallas interpreter
    anywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def vmem_limit_bytes(device_kind: str) -> int:
    """Scoped-VMEM budget of one kernel on a TPU of ``device_kind``.  Raises
    for a kind not in :data:`VMEM_BYTES_BY_KIND`."""
    if device_kind not in VMEM_BYTES_BY_KIND:
        raise ValueError(
            f"unknown TPU device kind {device_kind!r}: add its VMEM size to "
            "repro.kernels.common.VMEM_BYTES_BY_KIND"
        )
    return int(VMEM_BYTES_BY_KIND[device_kind] * VMEM_LIMIT_FRACTION)


# share of the device's HBM the relayout copies of one kernel call may take
KERNEL_HBM_FRACTION = 0.25


def kernel_row_limit(hbm_bytes: int, widths, block_rows: int) -> int:
    """Most rows one compiled Pallas call may take on a device with
    ``hbm_bytes`` of HBM: a power of two, never below ``block_rows``.

    A TPU keeps a narrow ``(rows, w)`` int32 array compact in HBM (rows on
    the lanes), but a Pallas call gets each row-indexed operand and output
    as a row-major copy padded to whole 128-lane tiles: ``rows * 512 B`` for
    every ``w <= 128``.  ``widths`` lists the word width of each of them;
    their copies together must fit :data:`KERNEL_HBM_FRACTION` of the HBM.
    """
    row_bytes = sum(-(-w // 128) for w in widths) * 128 * 4
    fit = int(hbm_bytes * KERNEL_HBM_FRACTION) // row_bytes
    limit = block_rows
    while limit * 2 <= fit:
        limit *= 2
    return limit


def decode(x: jax.Array, dtype: str) -> jax.Array:
    """Reinterpret raw int32 storage words as the column's 4-byte dtype."""
    if dtype == "float32":
        return jax.lax.bitcast_convert_type(x, jnp.float32)
    if dtype == "int32":
        return x
    raise ValueError(f"4-byte numeric column required, got {dtype}")


def pred_mask(vals: jax.Array, op: str, k: jax.Array) -> jax.Array:
    """The fused predicate every offload kernel evaluates in-scan."""
    if op == "gt":
        return vals > k
    if op == "lt":
        return vals < k
    if op == "none":
        return jnp.ones(vals.shape, dtype=bool)
    raise ValueError(op)


def tile_row_ids(i, block_rows: int) -> jax.Array:
    """Global row index of each row in grid step ``i``'s row tile, ``(B,)``.

    Taken from a ``(B, 1)`` iota, so it has the layout Mosaic gives a row-tile
    column ``x_ref[:, w]``: a mask built from it alone (the ``none``
    predicate) can still be widened to ``(B, 1)``, which a 1-D iota's mask
    cannot ("unsupported shape cast").
    """
    return i * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, 1), 0)[:, 0]


def group_ids(raw: jax.Array, num_groups: int) -> jax.Array:
    """The one group-key lowering every group-by path shares.

    Raw int32 storage words map to ``[0, num_groups)`` by floored modulo —
    the sign follows the (positive) divisor, so negative keys land in-range
    instead of producing negative group ids, and int32 overflow keys wrap the
    same way on every path.  The fused Pallas kernel, the XLA fallback, the
    single-op ``groupby_sum`` kernel, the host-path planner fallback, the
    reference oracle, and the sharded ``dist_groupby`` all call this one
    definition, so sharded and fused group-bys agree bit-for-bit on every
    key, however hostile.
    """
    return jnp.remainder(raw, num_groups)


def pad_rows(words: jax.Array, block_rows: int) -> jax.Array:
    """Zero-pad the row dimension to a whole number of row tiles."""
    n = words.shape[0]
    pad = (-n) % block_rows
    if pad:
        words = jnp.pad(words, ((0, pad), (0, 0)))
    return words


def column_slices(geom: TableGeometry):
    """(src_word_offset, dst_word_offset, word_width) per enabled column."""
    return tuple(
        zip(geom.col_word_offsets, geom.out_word_offsets, geom.col_word_widths)
    )


def pred_k_bits(pred_k, pred_dtype: str) -> jax.Array:
    """The predicate constant as int32 bits (how kernels take it as operand)."""
    k_arr = jnp.asarray(
        pred_k, dtype=jnp.float32 if pred_dtype == "float32" else jnp.int32
    )
    return jax.lax.bitcast_convert_type(k_arr, jnp.int32)
