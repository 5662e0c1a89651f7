"""Heterogeneous one-pass scan — filters, aggregates, group-bys, and
projections fused into the shared multi-view row-store pass.

``rme_project_multi`` made "scan once, answer everything" true for
*projections*: one Fetch-Unit stream per table per batch, every view's packed
block emitted from it.  But a mixed query tick is not all projections — the
paper's §8 extension argument (selection, aggregation, group-by offload) puts
every relational operator on that same stream, and the single-op kernels
(``rme_aggregate``, ``rme_filter``, ``groupby_sum``) each launch their own
full sweep of the row store.  N op kinds ⇒ N passes, which defeats the
amortization the whole design is built on.

This module closes that gap.  A **scan request** describes what one consumer
wants from the stream:

* :class:`ProjectRequest`   — a packed column-group block (what
  ``rme_project`` emits),
* :class:`FilterRequest`    — the packed block with predicate-failing rows
  zeroed plus a validity bitmap (``rme_filter``'s contract),
* :class:`AggregateRequest` — a partial ``[sum, count]`` scalar pair
  (``rme_aggregate``'s contract),
* :class:`GroupByRequest`   — partial per-group ``[sum, count]`` vectors
  (``groupby_sum``'s contract, one-hot MXU contraction).

:func:`scan_multi` lowers any mix of requests to **one** Pallas grid pass:
each row tile is streamed through VMEM once and every request's output is
emitted from that single visit — blocked outputs for projections/filters,
accumulated outputs for aggregates/group-bys.  MVCC snapshot tests and
padded-row masking are fused per request exactly as in the single-op kernels.
``scan_multi_xla`` is the fused-gather fallback for non-TPU lowering: one
gather of the union of every request's enabled words, then per-request
compute out of that shared array.

Byte accounting follows the same union discipline: :func:`union_geometry`
builds the one accounting geometry covering all requests' enabled words
(including predicate and hidden MVCC timestamp words), so the engine charges
the fused pass's bus beats exactly once (Eq. (3) bursts over the union).

Only the MLP formulation applies (whole-row tiles through the double-buffered
pipeline); as with ``rme_project_multi``, the BSL/PCK revisions route their
batched work through this kernel too, and ``revision="xla"`` dispatches the
fallback.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schema import WORD, TableGeometry, geometry_from_intervals

from .common import (
    DEFAULT_BLOCK_ROWS,
    column_slices,
    decode,
    group_ids,
    pad_rows,
    pred_k_bits,
    pred_mask,
    resolve_interpret,
    tile_row_ids,
)


# ------------------------------------------------------------ scan requests
@dataclasses.dataclass(frozen=True)
class ProjectRequest:
    """A packed column-group block: ``(N, out_words)`` int32."""

    geom: TableGeometry


@dataclasses.dataclass(frozen=True)
class FilterRequest:
    """Packed block with failing rows zeroed + bool validity mask."""

    geom: TableGeometry
    pred_word: int
    pred_dtype: str = "int32"
    pred_op: str = "gt"
    pred_k: int | float = 0
    ts_word: int = -1  # >= 0 fuses the MVCC snapshot test
    ts: int = 0


@dataclasses.dataclass(frozen=True)
class AggregateRequest:
    """``[sum, count]`` float32 pair over the predicate-passing rows."""

    agg_word: int
    agg_dtype: str = "int32"
    pred_word: int = 0
    pred_dtype: str = "int32"
    pred_op: str = "none"
    pred_k: int | float = 0
    ts_word: int = -1
    ts: int = 0


@dataclasses.dataclass(frozen=True)
class GroupByRequest:
    """Per-group ``(sums[G], counts[G])`` over a static group domain."""

    group_word: int
    agg_word: int
    num_groups: int
    agg_dtype: str = "int32"
    pred_word: int = 0
    pred_dtype: str = "int32"
    pred_op: str = "none"
    pred_k: int | float = 0
    ts_word: int = -1
    ts: int = 0


ScanRequest = ProjectRequest | FilterRequest | AggregateRequest | GroupByRequest


def _strip_dynamic(req: ScanRequest) -> ScanRequest:
    """Normalize everything the kernel doesn't consume out of the static spec
    so it never retraces for it: the traced operands (predicate constant,
    snapshot time) and the geometry's ``row_count`` — output shapes follow
    the *words* operand, so a growing table (the HTAP ingest pattern: every
    tick appends a few rows) reuses one trace per chunk shape instead of
    recompiling every request every tick."""
    if isinstance(req, (ProjectRequest, FilterRequest)):
        req = dataclasses.replace(
            req, geom=dataclasses.replace(req.geom, row_count=0)
        )
    if isinstance(req, ProjectRequest):
        return req
    return dataclasses.replace(req, pred_k=0, ts=0)


def request_intervals(req: ScanRequest) -> list[tuple[int, int]]:
    """Byte intervals of the row-store words this request enables.

    This is the request's footprint on the Fetch-Unit stream: projected
    columns, the predicate word, the aggregate/group words, and the two
    hidden MVCC timestamp words when a snapshot test is fused.  The engine
    merges these across a batch into the one union accounting geometry.
    """
    spans: list[tuple[int, int]] = []
    if isinstance(req, (ProjectRequest, FilterRequest)):
        spans.extend(zip(req.geom.abs_offsets, req.geom.col_widths))
    if isinstance(req, AggregateRequest):
        spans.append((req.agg_word * WORD, WORD))
    if isinstance(req, GroupByRequest):
        spans.append((req.group_word * WORD, WORD))
        spans.append((req.agg_word * WORD, WORD))
    if not isinstance(req, ProjectRequest):
        if req.pred_op != "none":
            spans.append((req.pred_word * WORD, WORD))
        if req.ts_word >= 0:
            spans.append((req.ts_word * WORD, 2 * WORD))
    return spans


def union_geometry(
    requests: Sequence[ScanRequest], row_bytes: int, row_count: int
) -> TableGeometry:
    """The one accounting geometry covering every request's enabled words.

    Overlapping/adjacent intervals collapse into single burst chains via the
    shared charging rule (:func:`repro.core.schema.geometry_from_intervals`)
    — the fused pass's bus beats are charged once for the whole batch.
    """
    intervals = [
        (o, w) for req in requests for o, w in request_intervals(req)
    ]
    if not intervals:
        raise ValueError("union_geometry needs at least one enabled word")
    return geometry_from_intervals(intervals, row_bytes=row_bytes,
                                   row_count=row_count)


def scan_vmem_footprint_bytes(
    requests: Sequence[ScanRequest], row_words: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> int:
    """Modeled VMEM working set of one fused grid step (2 MB SPM budget).

    The row tile and every blocked output are double-buffered (Pallas
    pipeline); accumulator outputs (aggregates, group-by partials) are tiny
    and resident for the whole pass.
    """
    total = 2 * block_rows * row_words * 4  # double-buffered row tile
    for req in requests:
        if isinstance(req, ProjectRequest):
            total += 2 * block_rows * req.geom.out_words_per_row * 4
        elif isinstance(req, FilterRequest):
            total += 2 * block_rows * (req.geom.out_words_per_row + 1) * 4
        elif isinstance(req, AggregateRequest):
            total += 2 * 4
        else:
            total += req.num_groups * 2 * 4
    return total


# ------------------------------------------------------------ Pallas kernel
def _fused_mask(req, i, block_rows, n_rows, x_ref, k_ref, ts_ref, r):
    """The per-request row mask: predicate & padded-tail & MVCC snapshot."""
    k = decode(k_ref[r, 0], req.pred_dtype)
    mask = pred_mask(decode(x_ref[:, req.pred_word], req.pred_dtype),
                     req.pred_op, k)
    ridx = tile_row_ids(i, block_rows)
    mask = mask & (ridx < n_rows)
    if req.ts_word >= 0:
        ts = ts_ref[r, 0]
        mask = mask & (x_ref[:, req.ts_word] <= ts) & (ts < x_ref[:, req.ts_word + 1])
    return mask


def _scan_multi_kernel(requests, n_rows, x_ref, k_ref, ts_ref, *o_refs):
    i = pl.program_id(0)
    block_rows = x_ref.shape[0]
    oi = 0
    for r, req in enumerate(requests):
        if isinstance(req, ProjectRequest):
            parts = [x_ref[:, s : s + w] for s, _, w in column_slices(req.geom)]
            o_refs[oi][...] = jnp.concatenate(parts, axis=1)
            oi += 1
            continue
        mask = _fused_mask(req, i, block_rows, n_rows, x_ref, k_ref, ts_ref, r)
        if isinstance(req, FilterRequest):
            parts = [x_ref[:, s : s + w] for s, _, w in column_slices(req.geom)]
            packed = jnp.concatenate(parts, axis=1)
            o_refs[oi][...] = jnp.where(mask[:, None], packed, 0)
            o_refs[oi + 1][...] = mask[:, None].astype(jnp.int32)
            oi += 2
            continue
        o_ref = o_refs[oi]
        oi += 1

        @pl.when(i == 0)
        def _init(o_ref=o_ref):
            o_ref[...] = jnp.zeros_like(o_ref)

        vals = decode(x_ref[:, req.agg_word], req.agg_dtype).astype(jnp.float32)
        fm = mask.astype(jnp.float32)
        contrib = jnp.stack([vals * fm, fm], axis=1)  # (B, 2)
        if isinstance(req, AggregateRequest):
            # a (1, 2) vector store: Mosaic cannot store scalars to VMEM
            o_ref[...] += jnp.sum(contrib, axis=0, keepdims=True)
        else:  # GroupByRequest: one-hot × matmul MXU contraction
            g = group_ids(x_ref[:, req.group_word], req.num_groups)
            onehot = (
                g[:, None] == jax.lax.iota(jnp.int32, req.num_groups)[None, :]
            ).astype(jnp.float32)  # (B, G)
            o_ref[...] += jax.lax.dot_general(
                onehot, contrib, (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )  # (G, 2)


def _check_requests(row_words: int, requests: Sequence[ScanRequest]) -> None:
    if not requests:
        raise ValueError("scan_multi needs at least one request")
    for req in requests:
        if isinstance(req, (ProjectRequest, FilterRequest)):
            if row_words < req.geom.row_words:
                raise ValueError(
                    f"storage rows {row_words}w < geometry rows {req.geom.row_words}w"
                )


@functools.partial(
    jax.jit,
    static_argnames=("requests", "block_rows", "interpret", "vmem_limit"),
)
def _scan_multi(
    words: jax.Array,
    k_bits: jax.Array,  # (R, 1) int32: per-request predicate constant bits
    ts_arr: jax.Array,  # (R, 1) int32: per-request snapshot times
    requests: tuple[ScanRequest, ...],
    block_rows: int,
    interpret: bool,
    vmem_limit: int | None = None,
):
    n, row_words = words.shape
    x = pad_rows(words, block_rows)
    n_pad = x.shape[0]
    n_req = len(requests)

    out_specs: list[pl.BlockSpec] = []
    out_shape: list[jax.ShapeDtypeStruct] = []
    for req in requests:
        if isinstance(req, (ProjectRequest, FilterRequest)):
            w = req.geom.out_words_per_row
            out_specs.append(pl.BlockSpec((block_rows, w), lambda i: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((n_pad, w), jnp.int32))
            if isinstance(req, FilterRequest):
                out_specs.append(pl.BlockSpec((block_rows, 1), lambda i: (i, 0)))
                out_shape.append(jax.ShapeDtypeStruct((n_pad, 1), jnp.int32))
        elif isinstance(req, AggregateRequest):
            out_specs.append(pl.BlockSpec((1, 2), lambda i: (0, 0)))
            out_shape.append(jax.ShapeDtypeStruct((1, 2), jnp.float32))
        else:
            out_specs.append(
                pl.BlockSpec((req.num_groups, 2), lambda i: (0, 0))
            )
            out_shape.append(
                jax.ShapeDtypeStruct((req.num_groups, 2), jnp.float32)
            )

    return pl.pallas_call(
        functools.partial(_scan_multi_kernel, requests, n),
        grid=(n_pad // block_rows,),
        name="rme_scan_multi",
        in_specs=[
            pl.BlockSpec((block_rows, row_words), lambda i: (i, 0)),
            pl.BlockSpec((n_req, 1), lambda i: (0, 0)),
            pl.BlockSpec((n_req, 1), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=(None if vmem_limit is None else
                         pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)),
        interpret=resolve_interpret(interpret),
    )(x, k_bits, ts_arr)


def _unflatten(requests, flat, n):
    """Regroup the pallas outputs into each request's natural result shape."""
    results, fi = [], 0
    for req in requests:
        if isinstance(req, ProjectRequest):
            results.append(flat[fi][:n])
            fi += 1
        elif isinstance(req, FilterRequest):
            results.append((flat[fi][:n], flat[fi + 1][:n, 0].astype(bool)))
            fi += 2
        elif isinstance(req, AggregateRequest):
            results.append(flat[fi][0])
            fi += 1
        else:
            results.append((flat[fi][:, 0], flat[fi][:, 1]))
            fi += 1
    return results


def scan_multi(
    words: jax.Array,
    requests: Sequence[ScanRequest],
    revision: str = "mlp",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
    vmem_limit: int | None = None,
) -> list:
    """One row-store pass serving a heterogeneous request batch.

    Returns one result per request, in order, each matching its single-op
    kernel's contract: ``(N, out_words)`` packed blocks for projections,
    ``(packed, bool mask)`` pairs for filters, float32 ``[sum, count]`` for
    aggregates, and ``(sums[G], counts[G])`` for group-bys.  The predicate
    constants and snapshot times are traced operands — distinct values do not
    retrace the kernel.  ``vmem_limit`` sets the compiled kernel's
    scoped-VMEM limit (the budget its row tile was sized against).
    """
    if revision == "xla":
        return scan_multi_xla(words, tuple(requests))
    n, row_words = words.shape
    _check_requests(row_words, requests)
    k_bits, ts_arr = _dynamic_operands(requests)
    flat = _scan_multi(
        words, k_bits, ts_arr, tuple(_strip_dynamic(r) for r in requests),
        block_rows, interpret, vmem_limit,
    )
    return _unflatten(requests, flat, n)


def combine_chunk_outputs(req: ScanRequest, parts: Sequence) -> object:
    """Merge one request's per-chunk outputs into its whole-table result.

    The delta-chunked row store (``repro.core.engine.DeviceRowStore``) keeps
    a table as a base chunk plus appended tail chunks; a fused pass streams
    each chunk independently and this is the combine rule — the reason it is
    *possible* is that every request kind is either row-local (blocked
    outputs: rows of chunk k land at their global offsets, so concatenation
    reassembles the table order) or an associative reduction (aggregate /
    group-by partials add, exactly how the single-chunk kernel already
    combines its row tiles).  MVCC snapshot tests are per-row, so chunk
    boundaries never change visibility.
    """
    if isinstance(req, ProjectRequest):
        return jnp.concatenate(list(parts), axis=0)
    if isinstance(req, FilterRequest):
        return (jnp.concatenate([p[0] for p in parts], axis=0),
                jnp.concatenate([p[1] for p in parts], axis=0))
    if isinstance(req, AggregateRequest):
        total = parts[0]
        for p in parts[1:]:
            total = total + p
        return total
    sums, counts = parts[0]
    for s, c in parts[1:]:
        sums, counts = sums + s, counts + c
    return sums, counts


def scan_multi_chunked(
    chunks: Sequence[jax.Array],
    requests: Sequence[ScanRequest],
    revision: str = "mlp",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> list:
    """One fused pass per resident chunk, combined into per-request results.

    ``chunks`` are consecutive row ranges of one table's row store (base +
    appended tails); each is streamed through :func:`scan_multi` once and the
    per-chunk outputs merge via :func:`combine_chunk_outputs`.  A single
    chunk degenerates to exactly ``scan_multi`` — the common (write-free)
    case pays nothing for the chunked formulation.
    """
    if len(chunks) == 1:
        return scan_multi(chunks[0], requests, revision=revision,
                          block_rows=block_rows, interpret=interpret)
    per_chunk = [
        scan_multi(chunk, requests, revision=revision,
                   block_rows=block_rows, interpret=interpret,
                   vmem_limit=vmem_limit)
        for chunk in chunks
    ]
    return [
        combine_chunk_outputs(req, [outs[r] for outs in per_chunk])
        for r, req in enumerate(requests)
    ]


def reduced_result_bytes(req: ScanRequest) -> int | None:
    """Bytes of one request's *reduced* partial, or ``None`` for blocked kinds.

    This is the unit of the sharded backend's interconnect accounting: when
    per-shard fused passes combine via :func:`combine_chunk_outputs`, an
    aggregate ships its float32 ``[sum, count]`` pair (8 bytes) and a
    group-by its ``(G, 2)`` partial — never anything proportional to the
    shard's row count.  Blocked outputs (projections, filters) return
    ``None``: they stay shard-resident until finalize and are charged to
    ``bytes_to_cpu`` like any packed view, not to the collective.
    """
    if isinstance(req, AggregateRequest):
        return 2 * 4
    if isinstance(req, GroupByRequest):
        return req.num_groups * 2 * 4
    return None


def scan_shard(
    chunks: Sequence[jax.Array],
    requests: Sequence[ScanRequest],
    revision: str = "mlp",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
    vmem_limit: int | None = None,
) -> list[list]:
    """Shard-local entry point: one fused pass over each resident chunk of
    one shard (bank), per-chunk outputs left **uncombined**.

    The sharded engine needs the per-chunk granularity — blocked outputs are
    reassembled into global row order from each chunk's ownership segments,
    and reduced partials combine shard-locally before anything crosses the
    interconnect — so unlike :func:`scan_multi_chunked` this returns
    ``[chunk][request]`` raw outputs.  Every pass is an ordinary
    single-device :func:`scan_multi` on the shard's own device: requests are
    row-position-local, so no SPMD lowering is required and the Pallas
    revisions work per shard exactly as they do per chunk.
    """
    return [
        scan_multi(chunk, requests, revision=revision,
                   block_rows=block_rows, interpret=interpret,
                   vmem_limit=vmem_limit)
        for chunk in chunks
    ]


def _dynamic_operands(requests: Sequence[ScanRequest]) -> tuple[jax.Array, jax.Array]:
    """Per-request (k_bits, ts) operand columns — traced, never static."""
    k_bits = jnp.stack(
        [pred_k_bits(getattr(r, "pred_k", 0), getattr(r, "pred_dtype", "int32"))
         for r in requests]
    ).reshape(len(requests), 1)
    ts_arr = jnp.asarray(
        [getattr(r, "ts", 0) for r in requests], dtype=jnp.int32
    ).reshape(len(requests), 1)
    return k_bits, ts_arr


# ------------------------------------------------------------- XLA fallback
def scan_multi_xla(words: jax.Array, requests: tuple[ScanRequest, ...]) -> list:
    """Fused-gather fallback: gather the union of enabled words once, then
    compute every request's output from that single shared pass.  Like the
    Pallas path, predicate constants and snapshot times travel as traced
    operands — distinct values never retrace."""
    _check_requests(words.shape[1], requests)
    k_bits, ts_arr = _dynamic_operands(requests)
    return _scan_multi_xla(
        words, k_bits, ts_arr, tuple(_strip_dynamic(r) for r in requests)
    )


@functools.partial(jax.jit, static_argnames=("requests",))
def _scan_multi_xla(
    words: jax.Array,
    k_bits: jax.Array,
    ts_arr: jax.Array,
    requests: tuple[ScanRequest, ...],
) -> list:
    union: list[int] = []
    seen: set[int] = set()
    for req in requests:
        for off, w in request_intervals(req):
            for word in range(off // WORD, (off + w) // WORD):
                if word not in seen:
                    seen.add(word)
                    union.append(word)
    union.sort()
    pos = {word: i for i, word in enumerate(union)}
    shared = jnp.take(words, jnp.asarray(union, dtype=jnp.int32), axis=1)

    def col(word: int) -> jax.Array:
        return shared[:, pos[word]]

    def mask_of(req, r: int) -> jax.Array:
        if req.pred_op != "none":
            k = decode(k_bits[r, 0], req.pred_dtype)
            m = pred_mask(decode(col(req.pred_word), req.pred_dtype),
                          req.pred_op, k)
        else:
            m = jnp.ones(shared.shape[:1], dtype=bool)
        if req.ts_word >= 0:
            ts = ts_arr[r, 0]
            m = m & (col(req.ts_word) <= ts) & (ts < col(req.ts_word + 1))
        return m

    def packed_of(geom: TableGeometry) -> jax.Array:
        idx = []
        for off, w in zip(geom.col_word_offsets, geom.col_word_widths):
            idx.extend(pos[word] for word in range(off, off + w))
        return jnp.take(shared, jnp.asarray(idx, dtype=jnp.int32), axis=1)

    results = []
    for r, req in enumerate(requests):
        if isinstance(req, ProjectRequest):
            results.append(packed_of(req.geom))
            continue
        if isinstance(req, FilterRequest):
            mask = mask_of(req, r)
            results.append((jnp.where(mask[:, None], packed_of(req.geom), 0), mask))
            continue
        mask = mask_of(req, r)
        vals = decode(col(req.agg_word), req.agg_dtype).astype(jnp.float32)
        fm = mask.astype(jnp.float32)
        if isinstance(req, AggregateRequest):
            results.append(jnp.stack([jnp.sum(vals * fm), jnp.sum(fm)]))
        else:
            g = group_ids(col(req.group_word), req.num_groups)
            sums = jax.ops.segment_sum(vals * fm, g, num_segments=req.num_groups)
            counts = jax.ops.segment_sum(fm, g, num_segments=req.num_groups)
            results.append((sums, counts))
    return results
