"""Mesh-sharded serving — rows sharded like parallel DRAM banks.

The paper exploits "the inherent parallelism of memory cells — e.g., by
issuing outstanding parallel requests to separate DRAM banks" (§1).  At
cluster scale the analogous parallelism is *row-range sharding across chips*:
each device owns a contiguous row range of the table (a "bank"), runs the RME
datapath locally, and only reduced results (scalars, group accumulators,
broadcast build sides) cross the interconnect.

Two layers live here:

* **Free sharded operators** (``dist_project`` / ``dist_aggregate`` /
  ``dist_groupby`` / ``dist_join``) — ``shard_map`` over an explicit mesh
  axis, so the same code lowers for the 1-device CPU test run, the 256-chip
  single-pod mesh, and the 512-chip multi-pod mesh (the dry-run exercises
  the latter two).  The engine datapath inside ``shard_map`` is the XLA
  fused-gather revision: Pallas interpret-mode kernels don't lower under
  SPMD partitioning on CPU, and on real TPUs the same call sites swap in
  the MLP kernel.
* **The sharded execution backend** (:class:`ShardedRowStore` +
  :class:`ShardedEngine`) — a first-class drop-in for the single-device
  engine.  Each shard keeps its own delta-chunked base+tail buffers
  (appends upload only to the owning shard, timestamp patches rewrite only
  the owning shard's words), a tick's one fused ``rme_scan_multi`` pass
  runs **per shard** as a plain per-device call (no SPMD lowering — every
  Pallas revision works per shard exactly as it does per chunk), and only
  reduced results cross the interconnect: aggregate/group-by partials
  combine via the kernel layer's associative
  :func:`~repro.kernels.rme_scan_multi.combine_chunk_outputs`, packed and
  filter blocks stay shard-resident until finalize, and joins broadcast
  only the (small) cached build-partition set.  ``EngineStats`` charges the
  interconnect explicitly (``bytes_collective`` / ``collective_ops``) —
  O(result/build) bytes by construction, never O(rows).

The serving loop's pipelined primitives are inherited unchanged:
``execute_many_async`` wraps this class's ``execute_many`` (whose per-shard
passes already enqueue without a host sync — blocking happens only when a
result is pulled), and ``stream_project`` iterates ``device_chunks``, which
:meth:`ShardedRowStore.chunks` yields in global row order (ownership
segments sorted by starting row), so streamed chunks concatenate to the
same packed block on both backends.
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ref as R
from repro.kernels import rme_join as KJ
from repro.kernels import rme_scan_multi as KR
from repro.kernels.common import group_ids
from repro.kernels.rme_project import project_xla

from . import faults
from .engine import (
    MAX_TAIL_CHUNKS,
    DeviceRowStore,
    EngineStats,
    RelationalMemoryEngine,
)
from .requests import JoinOp, JoinResult
from .schema import WORD, TableGeometry
from .table import RelationalTable


def _row_axes(mesh: Mesh, axes: str | Sequence[str]) -> tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def pad_rows_to(words: np.ndarray | jax.Array, shards: int) -> jax.Array:
    """Pad the row count to a multiple of ``shards`` with zero rows.

    Padding must be *masked*, never trusted to be inert: every sharded
    operator takes ``valid_rows`` (the true row count) and excludes padded
    positions explicitly — packed projections zero them, aggregates and
    group-bys drop them from the masked reduction, and the join refuses to
    match them on either side (a padded row's key word is 0, which is a
    perfectly legitimate key).  MVCC rows get a second, independent guard:
    ts_begin=0 <= ts < ts_end=0 can never hold.
    """
    n = words.shape[0]
    pad = (-n) % shards
    if pad:
        words = jnp.concatenate(
            [jnp.asarray(words), jnp.zeros((pad, words.shape[1]), words.dtype)], 0
        )
    return jnp.asarray(words)


def _shard_valid(axes: tuple[str, ...], shard_rows: int, n_valid) -> jax.Array:
    """Per-shard mask of globally-valid row positions (False on padding)."""
    idx = jax.lax.axis_index(axes)
    rows = idx * shard_rows + jnp.arange(shard_rows)
    return rows < n_valid


def dist_project(
    words: jax.Array,
    geom: TableGeometry,
    mesh: Mesh,
    axes: str | Sequence[str] = "data",
    valid_rows: int | None = None,
) -> jax.Array:
    """Row-sharded packed projection: each shard reorganizes its own bank.

    No cross-device traffic at all — the reorganized view stays sharded the
    same way the base table is, ready for downstream sharded consumers.
    ``valid_rows`` (the pre-padding row count) zeroes padded output rows so
    consumers never see fabricated rows.
    """
    axes = _row_axes(mesh, axes)
    n_valid = words.shape[0] if valid_rows is None else valid_rows

    def local(w):
        out = project_xla(w, geom)
        valid = _shard_valid(axes, w.shape[0], n_valid)
        return jnp.where(valid[:, None], out, 0)

    return shard_map(
        local, mesh=mesh, in_specs=P(axes, None), out_specs=P(axes, None)
    )(words)


def dist_aggregate(
    words: jax.Array,
    mesh: Mesh,
    agg_word: int,
    agg_dtype: str = "int32",
    pred_word: int = 0,
    pred_dtype: str = "int32",
    pred_op: str = "none",
    pred_k=0,
    valid_rows: int | None = None,
    axes: str | Sequence[str] = "data",
) -> jax.Array:
    """Distributed Q0/Q3: per-bank fused masked sum, one scalar ``psum``.

    ``valid_rows`` masks padding introduced by :func:`pad_rows_to`.
    Returns float32 ``[sum, count]`` replicated on every device.
    """
    axes = _row_axes(mesh, axes)
    n_total = words.shape[0]
    n_valid = n_total if valid_rows is None else valid_rows

    def local(w):
        valid = _shard_valid(axes, w.shape[0], n_valid)
        vals = R._decode(w[:, agg_word], agg_dtype).astype(jnp.float32)
        mask = R._predicate(R._decode(w[:, pred_word], pred_dtype), pred_op, pred_k)
        mask = mask & valid
        part = jnp.stack([jnp.sum(jnp.where(mask, vals, 0.0)), jnp.sum(mask)])
        return jax.lax.psum(part, axes)

    return shard_map(
        local, mesh=mesh, in_specs=P(axes, None), out_specs=P()
    )(words)


def dist_groupby(
    words: jax.Array,
    mesh: Mesh,
    group_word: int,
    agg_word: int,
    num_groups: int,
    agg_dtype: str = "int32",
    pred_word: int | None = None,
    pred_dtype: str = "int32",
    pred_op: str = "none",
    pred_k=0,
    valid_rows: int | None = None,
    axes: str | Sequence[str] = "data",
) -> tuple[jax.Array, jax.Array]:
    """Distributed Q4: per-bank one-hot contraction, (G,2) ``psum`` combine.

    Group ids come from the shared :func:`repro.kernels.common.group_ids`
    lowering — the same floored modulo every fused kernel and the reference
    oracle use, so sharded and fused group-bys agree on negative and
    overflowing keys.
    """
    axes = _row_axes(mesh, axes)
    n_valid = words.shape[0] if valid_rows is None else valid_rows

    def local(w):
        valid = _shard_valid(axes, w.shape[0], n_valid)
        g = group_ids(w[:, group_word], num_groups)
        vals = R._decode(w[:, agg_word], agg_dtype).astype(jnp.float32)
        mask = valid
        if pred_word is not None:
            mask = mask & R._predicate(
                R._decode(w[:, pred_word], pred_dtype), pred_op, pred_k
            )
        fm = mask.astype(jnp.float32)
        onehot = (g[:, None] == jnp.arange(num_groups)[None, :]).astype(jnp.float32)
        contrib = jnp.stack([vals * fm, fm], axis=1)
        acc = jax.lax.dot_general(
            onehot, contrib, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return jax.lax.psum(acc, axes)

    out = shard_map(local, mesh=mesh, in_specs=P(axes, None), out_specs=P())(words)
    return out[:, 0], out[:, 1]


def dist_join(
    s_words: jax.Array,
    r_words: jax.Array,
    mesh: Mesh,
    s_geom: TableGeometry,
    r_geom: TableGeometry,
    s_key_word: int,
    s_val_word: int,
    r_key_word: int,
    r_val_word: int,
    s_valid_rows: int | None = None,
    r_valid_rows: int | None = None,
    axes: str | Sequence[str] = "data",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed broadcast equi-join.

    Both tables are row-sharded.  Each shard RME-projects its slim {key, val}
    pair; the (small) build side R is all-gathered — the only collective — and
    every shard probes its local S rows.  Word offsets index the *packed*
    projected views.  Returns sharded (s_val, matched r_val, match mask).

    Padding discipline: padded rows carry key word 0, and 0 is a legitimate
    key, so both sides carry explicit validity.  The gathered build side is
    sorted valid-rows-first among equal keys (``lexsort``) so the probe's
    left-position lookup lands on a real row whenever one exists, and a
    match requires the build row *and* the probe row to be valid.
    """
    axes = _row_axes(mesh, axes)
    n_s = s_words.shape[0] if s_valid_rows is None else s_valid_rows
    n_r = r_words.shape[0] if r_valid_rows is None else r_valid_rows

    def local(s_w, r_w):
        s_p = project_xla(s_w, s_geom)
        r_p = project_xla(r_w, r_geom)
        s_valid = _shard_valid(axes, s_w.shape[0], n_s)
        r_valid_local = _shard_valid(axes, r_w.shape[0], n_r)
        r_all = jax.lax.all_gather(r_p, axes, tiled=True)  # broadcast build side
        r_valid = jax.lax.all_gather(r_valid_local, axes, tiled=True)
        r_key, r_val = r_all[:, r_key_word], r_all[:, r_val_word]
        s_key, s_val = s_p[:, s_key_word], s_p[:, s_val_word]
        # primary sort by key; valid rows first among equal keys, so the
        # left position of a present key is always its valid copy
        order = jnp.lexsort((~r_valid, r_key))
        rk, rv, rva = r_key[order], r_val[order], r_valid[order]
        pos = jnp.clip(jnp.searchsorted(rk, s_key), 0, rk.shape[0] - 1)
        matched = (rk[pos] == s_key) & rva[pos] & s_valid
        return (
            jnp.where(s_valid, s_val, 0),
            jnp.where(matched, rv[pos], 0),
            matched,
        )

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None)),
        out_specs=(P(axes), P(axes), P(axes)),
    )(s_words, r_words)


def table_sharding(mesh: Mesh, axes: str | Sequence[str] = "data") -> NamedSharding:
    """Row-range sharding for a table buffer (rows over the data axis)."""
    return NamedSharding(mesh, P(_row_axes(mesh, axes), None))


# ===================================================================== backend
def shard_ranges(n_rows: int, shards: int) -> tuple[tuple[int, int], ...]:
    """Contiguous balanced row ranges: ``(start, n)`` per shard.

    The first ``n_rows % shards`` shards take one extra row, so shard sizes
    differ by at most one and their concatenation is ``[0, n_rows)`` in
    order — the row-range ownership map of the sharded backend.
    """
    base, extra = divmod(n_rows, shards)
    out, start = [], 0
    for s in range(shards):
        n = base + (1 if s < extra else 0)
        out.append((start, n))
        start += n
    return tuple(out)


@dataclasses.dataclass
class _ShardChunk:
    """One shard-resident buffer: rows the shard owns, with their global ids.

    ``segments`` maps the chunk's local rows, in order, back to global row
    ranges ``(global_start, n_rows)``.  A freshly uploaded chunk has one
    segment; shard-local compaction concatenates chunk buffers device-side
    and their segment lists along with them, so ownership survives merging
    of non-adjacent ranges (round-robin appends make a shard's ranges
    non-contiguous).
    """

    words: jax.Array
    segments: tuple[tuple[int, int], ...]

    @property
    def rows(self) -> int:
        return self.words.shape[0]


@dataclasses.dataclass
class _ShardedEntry:
    """One table's sharded device residency: per-shard chunk lists.

    ``rows`` / ``patch_seq`` are the same sync watermarks as the
    single-device ``_StoreEntry`` (the base class's ``contains`` reads them
    unchanged); ``next_owner`` round-robins append ownership so sustained
    ingest spreads across banks.
    """

    shards: list[list[_ShardChunk]]
    rows: int
    patch_seq: int
    next_owner: int = 0


class ShardedRowStore(DeviceRowStore):
    """Per-shard delta-chunked row-store buffers — one bank per shard.

    The single-device :class:`DeviceRowStore` keeps a table as base + tail
    chunks on one device; this subclass splits the base into one contiguous
    row range per shard (:func:`shard_ranges`) and keeps the whole delta
    machinery *per shard*:

    * a **full upload** places each shard's range on that shard's device
      (``devices[s]``; ``None`` = logical shard on the default device),
    * an **append** uploads the new tail rows to exactly one owning shard
      (round-robin), O(new rows) bytes to one bank — no other shard moves,
    * a **delete/update** replays the patch log against only the chunks
      whose segments own the touched rows — O(touched rows) words,
    * **compaction** is shard-local and device-side (charges nothing).

    Host-side consumers (``get`` / ``tail`` / ``chunks``) reassemble global
    row order from the ownership segments, gathering to the root device;
    these gathers model the host-side merge of per-bank results and are
    charged by their callers (``bytes_to_cpu``), not as collectives.  The
    scan path never pays them: :meth:`shard_parts` hands the engine the raw
    per-shard chunk lists.
    """

    def __init__(self, stats: EngineStats | None = None, delta: bool = True,
                 num_shards: int = 1, devices: Sequence | None = None):
        super().__init__(stats, delta=delta)
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self.num_shards = num_shards
        self._devices = (list(devices) if devices is not None
                         else [None] * num_shards)
        if len(self._devices) != num_shards:
            raise ValueError("devices must have one entry per shard")
        self._root = next((d for d in self._devices if d is not None), None)

    # ---------------------------------------------------------- placement
    def _place(self, arr: jax.Array, shard: int) -> jax.Array:
        dev = self._devices[shard]
        return arr if dev is None else jax.device_put(arr, dev)

    def _to_root(self, arr: jax.Array) -> jax.Array:
        return arr if self._root is None else jax.device_put(arr, self._root)

    # ----------------------------------------------------------------- sync
    def _full_upload(self, table: RelationalTable) -> _ShardedEntry:
        faults.maybe_fault("upload", table=table.uid, delta=False)
        host = table.words()
        shards: list[list[_ShardChunk]] = [[] for _ in range(self.num_shards)]
        for s, (start, n) in enumerate(
            shard_ranges(table.row_count, self.num_shards)
        ):
            if n:
                shards[s].append(_ShardChunk(
                    self._place(jnp.asarray(host[start:start + n]), s),
                    ((start, n),),
                ))
        ent = _ShardedEntry(shards, table.row_count, table.mutation_version)
        if table.uid not in self._finalized:
            weakref.finalize(
                table, self._finalize_entry, weakref.ref(self), table.uid
            )
            self._finalized.add(table.uid)
        self._buffers[table.uid] = ent
        self._charge(host.size * host.itemsize, is_delta=False)
        return ent

    def _apply_patches(self, ent: _ShardedEntry, table: RelationalTable,
                       patches: list[np.ndarray]) -> int:
        """Rewrite patched ``__ts_end`` words inside the owning shards only.

        Global patch indices route through each chunk's ownership segments;
        a shard owning none of the touched rows is never touched itself.
        Returns the bytes shipped (one word per patched row).
        """
        idx = np.concatenate([p[p < ent.rows] for p in patches]) if patches else \
            np.empty(0, dtype=np.int64)
        if idx.size == 0:
            return 0
        vals = np.asarray(table.ts_end_at(idx))
        ts_word = table.ts_end_word
        for chunks in ent.shards:
            for c, chunk in enumerate(chunks):
                local, lvals, off = [], [], 0
                for g0, n in chunk.segments:
                    sel = (idx >= g0) & (idx < g0 + n)
                    if sel.any():
                        local.append(idx[sel] - g0 + off)
                        lvals.append(vals[sel])
                    off += n
                if local:
                    li = np.concatenate(local)
                    lv = np.concatenate(lvals)
                    chunks[c] = _ShardChunk(
                        chunk.words.at[jnp.asarray(li), ts_word].set(
                            jnp.asarray(lv)
                        ),
                        chunk.segments,
                    )
        return idx.size * WORD

    def _sync(self, table: RelationalTable) -> _ShardedEntry:
        """Bring the sharded copy current: deltas land only in owning shards."""
        ent = self._buffers.get(table.uid)
        if ent is not None and not self.delta and (
            ent.rows != table.row_count
            or ent.patch_seq != table.mutation_version
        ):
            ent = None  # baseline mode: any change → whole-table re-upload
        if ent is None:
            return self._full_upload(table)
        patches = (table.patches_since(ent.patch_seq)
                   if ent.patch_seq != table.mutation_version else [])
        if patches is None:  # lagged past the trimmed patch log: full re-sync
            return self._full_upload(table)
        if patches or table.row_count > ent.rows:
            # before any entry mutation: a fault here leaves every shard at
            # its pre-sync state, so a bare retry re-syncs cleanly
            faults.maybe_fault("upload", table=table.uid, delta=True)
        moved = self._apply_patches(ent, table, patches)
        ent.patch_seq = table.mutation_version
        if table.row_count > ent.rows:
            tail = table.tail_words(ent.rows)
            owner = ent.next_owner
            ent.shards[owner].append(_ShardChunk(
                self._place(jnp.asarray(tail), owner),
                ((ent.rows, tail.shape[0]),),
            ))
            ent.next_owner = (owner + 1) % self.num_shards
            ent.rows = table.row_count
            moved += tail.size * tail.itemsize
        self._charge(moved, is_delta=True)
        for s, chunks in enumerate(ent.shards):
            if len(chunks) > MAX_TAIL_CHUNKS:
                # shard-local device-side compaction: segments ride along,
                # so merged non-adjacent ranges keep their global ids
                ent.shards[s] = [_ShardChunk(
                    jnp.concatenate([c.words for c in chunks], axis=0),
                    tuple(seg for c in chunks for seg in c.segments),
                )]
        return ent

    # ------------------------------------------------------------ accessors
    @staticmethod
    def _pieces(ent: _ShardedEntry) -> Iterator[tuple[int, jax.Array]]:
        """Every resident ``(global_start, rows)`` piece, unordered."""
        for chunks in ent.shards:
            for chunk in chunks:
                off = 0
                for start, n in chunk.segments:
                    yield start, chunk.words[off:off + n]
                    off += n

    def _gathered(self, ent: _ShardedEntry,
                  from_row: int = 0) -> list[jax.Array]:
        """Root-device pieces in global row order, from ``from_row`` on."""
        parts = []
        for start, w in sorted(self._pieces(ent), key=lambda p: p[0]):
            if start + w.shape[0] > from_row:
                parts.append(self._to_root(w[max(from_row - start, 0):]))
        return parts

    def get(self, table: RelationalTable) -> jax.Array:
        """The table's row store as one root-device array (synced first).

        The sharded layout stays authoritative — this is the host-side merge
        view for single-buffer consumers (validity masks, host fallbacks),
        assembled from the ownership segments on every call.
        """
        parts = self._gathered(self._sync(table))
        if not parts:
            return jnp.zeros((0, table.row_words), dtype=jnp.int32)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    def chunks(self, table: RelationalTable) -> tuple[jax.Array, ...]:
        """Global-order chunk views (synced first), for chunk-iterating
        consumers that are not shard-aware."""
        parts = self._gathered(self._sync(table))
        if not parts:
            return (jnp.zeros((0, table.row_words), dtype=jnp.int32),)
        return tuple(parts)

    def tail(self, table: RelationalTable, start_row: int) -> jax.Array:
        """Rows ``[start_row, row_count)`` in global order, on the root."""
        parts = self._gathered(self._sync(table), from_row=start_row)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    def shard_parts(self, table: RelationalTable) -> list[list[_ShardChunk]]:
        """The synced per-shard chunk lists — the sharded scan operand.

        Index ``s`` is shard ``s``'s resident chunks on its own device (an
        empty list for a shard that owns no rows yet); nothing is gathered.
        """
        return [list(chunks) for chunks in self._sync(table).shards]

    @property
    def occupancy_bytes(self) -> int:
        return sum(
            c.words.size * c.words.dtype.itemsize
            for ent in self._buffers.values()
            for chunks in ent.shards for c in chunks
        )


def _empty_scan_result(req: "KR.ScanRequest"):
    """The canonical output of a fused request over zero rows — what a
    0-row table (no chunks on any shard) must still answer with."""
    if isinstance(req, KR.ProjectRequest):
        return jnp.zeros((0, req.geom.out_words_per_row), jnp.int32)
    if isinstance(req, KR.FilterRequest):
        return (jnp.zeros((0, req.geom.out_words_per_row), jnp.int32),
                jnp.zeros((0,), bool))
    if isinstance(req, KR.AggregateRequest):
        return jnp.zeros(2, jnp.float32)
    return (jnp.zeros(req.num_groups, jnp.float32),
            jnp.zeros(req.num_groups, jnp.float32))


class ShardedEngine(RelationalMemoryEngine):
    """The mesh-sharded execution backend — same results, per-bank datapath.

    Drop-in for :class:`RelationalMemoryEngine`: the whole serving surface
    (``execute_many``, ``materialize``, the planner's physical routes, the
    ``QueryServer``) runs unchanged on top of two overridden hooks —

    * :meth:`_serve_scan` — a tick's fused request tuple runs as **one
      fused pass per shard** (plain per-device ``scan_multi`` calls over
      the shard's resident chunks; no SPMD lowering, so every Pallas
      revision and the XLA fallback work per shard exactly as per chunk).
      Aggregate/group-by partials combine shard-locally, then once across
      shards via the associative ``combine_chunk_outputs`` — those reduced
      partials are the *only* scan bytes crossing the interconnect, charged
      to ``bytes_collective``.  Packed/filter blocks stay shard-resident
      and reassemble into global row order only at finalize (charged as
      ``bytes_to_cpu`` by the existing accounting, like any packed view).
    * :meth:`_join_direct` — the build side's cached Fibonacci-hash
      partitions are broadcast once per build version to every shard (the
      join's only collective, O(build rows)); each shard probes its own
      rows in place.

    ``mesh`` places shard ``s``'s buffers on ``mesh.devices.flat[s]``;
    ``num_shards`` without a mesh runs the identical code path as logical
    shards on the current device (the 1-device CPU case).  Both must be
    byte-identical to the single-device engine; exact float equality of
    re-associated sums holds whenever the sums are exactly representable
    (int32 payloads below 2^24 — the engine's test envelope).
    """

    def __init__(self, mesh: Mesh | None = None,
                 num_shards: int | None = None,
                 shard_retries: int = 2,
                 retry_backoff_s: float = 0.0,
                 quarantine_after: int = 3,
                 quarantine_probe_every: int = 4,
                 **kwargs):
        super().__init__(**kwargs)
        if mesh is not None:
            devices = list(mesh.devices.flat)
            if num_shards is None:
                num_shards = len(devices)
            if num_shards > len(devices):
                raise ValueError(
                    f"num_shards={num_shards} exceeds mesh size {len(devices)}"
                )
            devices = devices[:num_shards]
        else:
            num_shards = 1 if num_shards is None else num_shards
            devices = [None] * num_shards
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        self.mesh = mesh
        self.num_shards = num_shards
        self._devices = devices
        self.rowstore = ShardedRowStore(
            self.stats, delta=self.delta,
            num_shards=num_shards, devices=devices,
        )
        # broadcast replicas of join build partitions, one set per build
        # version: (table uid, mutation version) -> (source parts, replicas)
        self._bcast_parts: dict[tuple, tuple] = {}
        # failover policy (docs/reliability.md): transient shard-pass faults
        # retry with exponential backoff, then — or immediately on a
        # permanent fault — the shard's chunks re-execute on the root
        # device; repeated failures quarantine the shard (straight to
        # failover) with periodic half-open probes back to health
        self.shard_retries = shard_retries
        self.retry_backoff_s = retry_backoff_s
        self.quarantine_after = quarantine_after
        self.quarantine_probe_every = quarantine_probe_every
        self._health = [
            {"state": "healthy", "failures": 0, "skips": 0}
            for _ in range(self.num_shards)
        ]

    @property
    def backend(self) -> str:
        return "sharded"

    def reset(self) -> None:
        """Single-device reset plus the per-shard broadcast-replica cache."""
        super().reset()
        self._bcast_parts.clear()

    # ------------------------------------------------------------- gathers
    def _to_root(self, x):
        """Move one (pytree of) array(s) to the root shard's device."""
        root = self._devices[0]
        return x if root is None else jax.device_put(x, root)

    # ------------------------------------------------------- the scan hook
    def _serve_scan(self, table: RelationalTable,
                    reqs: tuple["KR.ScanRequest", ...],
                    shared: bool = False) -> list:
        """One fused pass per shard; only reduced partials cross shards.

        Requests are chunk-agnostic (word offsets, row-position-local), so
        the identical lowered tuple streams over every shard's chunks.  A
        lone request takes the same path — per-bank parallelism applies to
        solo queries too, and the per-shard pass count stays exactly one
        (``shared`` is accepted for the base-class hook contract; the
        subsumption layer runs in ``execute_many`` before this hook, so
        both backends see the same covering-collapsed request set).

        Every per-shard pass runs through :meth:`_shard_pass` (bounded
        retry → root-device failover → quarantine), and the cross-shard
        combine of reduced partials through :meth:`_combine_collective` —
        both byte-identical to the healthy run by construction.
        """
        faults.maybe_fault("scan_launch", table=table.uid)
        shards = self.rowstore.shard_parts(table)
        vmem = next((self._vmem_budget(cs[0].words) for cs in shards if cs),
                    self.vmem_bytes)
        block_rows = self._fused_block_rows(reqs, table.row_words, vmem)
        per_shard: list[tuple[list[_ShardChunk], list[list]]] = []
        for s, chunks in enumerate(shards):
            if not chunks:
                continue
            outs = self._shard_pass(table, s, chunks, reqs, block_rows, vmem)
            per_shard.append((chunks, outs))
            for c in chunks:
                self.charge_scan(table, reqs, row_count=c.rows)
        self.stats.shared_scans += 1
        self.stats.rows_projected += table.row_count
        active = len(per_shard)
        results = []
        for r, req in enumerate(reqs):
            if not per_shard:
                # a 0-row table owns no chunks on any shard: emit the same
                # canonical empty/zero outputs the single-device pass yields
                results.append(self._to_root(_empty_scan_result(req)))
                continue
            reduced = KR.reduced_result_bytes(req)
            if reduced is not None:
                # shard-local combine first, then one cross-shard combine of
                # the O(result)-sized partials — the modeled collective
                partials = [
                    self._to_root(KR.combine_chunk_outputs(
                        req, [chunk_outs[r] for chunk_outs in outs]
                    ))
                    for _, outs in per_shard
                ]
                if active > 1:
                    self.stats.bytes_collective += (active - 1) * reduced
                    self.stats.collective_ops += 1
                    results.append(self._combine_collective(req, partials))
                else:
                    results.append(KR.combine_chunk_outputs(req, partials))
            else:
                # blocked output: reassemble global row order from the
                # ownership segments (finalize gather, not a collective)
                pieces = []
                for chunks, outs in per_shard:
                    for chunk, chunk_outs in zip(chunks, outs):
                        out = chunk_outs[r]
                        off = 0
                        for start, n in chunk.segments:
                            piece = (
                                (out[0][off:off + n], out[1][off:off + n])
                                if isinstance(req, KR.FilterRequest)
                                else out[off:off + n]
                            )
                            pieces.append((start, piece))
                            off += n
                pieces.sort(key=lambda p: p[0])
                parts = [self._to_root(p) for _, p in pieces]
                results.append(KR.combine_chunk_outputs(req, parts))
        return results

    # -------------------------------------------------- failover machinery
    def _shard_pass(self, table: RelationalTable, shard: int, chunks,
                    reqs: tuple["KR.ScanRequest", ...],
                    block_rows: int, vmem: int) -> list[list]:
        """One shard's fused pass with bounded retry, failover, quarantine.

        A transient fault retries up to ``shard_retries`` times with
        ``retry_backoff_s * 2**attempt`` backoff; a permanent fault — or
        retry exhaustion — re-executes this shard's chunks on the root
        device via :meth:`_failover_pass` (byte-identical results; the tick
        completes without the shard).  ``quarantine_after`` consecutive
        failed passes quarantine the shard: subsequent passes go straight
        to failover, with every ``quarantine_probe_every``-th pass probing
        the shard half-open.  A successful pass restores full health.
        """
        health = self._health[shard]
        if health["state"] == "quarantined":
            health["skips"] += 1
            if health["skips"] % self.quarantine_probe_every != 0:
                return self._failover_pass(shard, chunks, reqs)
        attempt = 0
        while True:
            try:
                faults.maybe_fault("shard_pass", shard=shard,
                                   table=table.uid)
                outs = KR.scan_shard(
                    [c.words for c in chunks], reqs,
                    revision=self.revision, block_rows=block_rows,
                    interpret=self.interpret, vmem_limit=vmem,
                )
            except Exception as err:
                permanent = isinstance(err, faults.PermanentFault)
                if not permanent and attempt < self.shard_retries:
                    self.stats.retries += 1
                    if self.retry_backoff_s:
                        time.sleep(self.retry_backoff_s * (2 ** attempt))
                    attempt += 1
                    continue
                health["failures"] += 1
                if health["failures"] >= self.quarantine_after:
                    health["state"] = "quarantined"
                return self._failover_pass(shard, chunks, reqs)
            health["state"] = "healthy"
            health["failures"] = 0
            health["skips"] = 0
            return outs

    def _failover_pass(self, shard: int, chunks,
                       reqs: tuple["KR.ScanRequest", ...]) -> list[list]:
        """Re-execute a failed shard's chunks on the root device.

        The fused-gather XLA path serves the same request tuple over the
        same chunk rows, so the per-chunk outputs — and everything combined
        from them — are byte-identical to the healthy shard pass (the
        xla-revision equality suite is the standing proof).  Charged as one
        ``failovers`` event plus the shard's row bytes re-shipped across
        the interconnect (``bytes_failover``).
        """
        outs = []
        moved = 0
        for c in chunks:
            words = self._to_root(c.words)
            outs.append(KR.scan_multi_xla(words, tuple(reqs)))
            moved += c.words.size * c.words.dtype.itemsize
        self.stats.failovers += 1
        self.stats.bytes_failover += moved
        return outs

    def _combine_collective(self, req: "KR.ScanRequest", partials):
        """The cross-shard combine with bounded transient retry.

        The partials are already materialized on the root device, so a
        retry just re-runs the O(result)-sized combine.  A permanent fault
        (or retry exhaustion) propagates typed — the serving layer turns it
        into a per-ticket error.
        """
        attempt = 0
        while True:
            try:
                faults.maybe_fault("collective_combine")
                return KR.combine_chunk_outputs(req, partials)
            except faults.TransientFault:
                if attempt >= self.shard_retries:
                    raise
                self.stats.retries += 1
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s * (2 ** attempt))
                attempt += 1

    def shard_health(self) -> list[str]:
        """Per-shard health states (``"healthy"`` / ``"quarantined"``)."""
        return [h["state"] for h in self._health]

    # ------------------------------------------------------- the join hook
    def _shard_partitions(self, right_table: RelationalTable, parts):
        """Broadcast replicas of the build partitions, one per shard.

        Cached per build-table version: the first probe after a build (or a
        build-side write) pays one ``(shards - 1) * parts.nbytes``
        interconnect charge; every warm probe reuses the device-resident
        replicas for free — the same residency contract as the partitions
        themselves.
        """
        key = (right_table.uid, right_table.mutation_version)
        hit = self._bcast_parts.get(key)
        if hit is not None and hit[0] is parts:
            return hit[1]
        replicas = KJ.broadcast_partitions(parts, self._devices)
        if self.num_shards > 1:
            self.stats.bytes_collective += (self.num_shards - 1) * parts.nbytes
            self.stats.collective_ops += 1
        self._bcast_parts[key] = (parts, replicas)
        return replicas

    def _join_direct(self, op: JoinOp) -> JoinResult:
        """Solo join, sharded: every shard probes its own rows in place.

        Only the broadcast build partitions cross the interconnect — probe
        rows never move, and the per-probe-row outputs reassemble into
        global row order exactly like blocked scan outputs.
        """
        table = op.table
        parts = self._op_partitions(op)
        replicas = self._shard_partitions(op.right_table, parts)
        shards = self.rowstore.shard_parts(table)
        key_word = table.schema.word_offset(op.key)
        val_word = table.schema.word_offset(op.left_proj)
        snap = op.snapshot_ts is not None
        ts_word = table.ts_begin_word if snap else -1
        acc_req = op.lower()  # its intervals are exactly the probe footprint
        self.stats.rows_projected += table.row_count
        pieces = []
        for s, chunks in enumerate(shards):
            for chunk in chunks:
                out = self._probe_join(
                    chunk.words, replicas[s], key_word, val_word, ts_word,
                    op.snapshot_ts or 0, snap,
                    route=(table.uid, "join"),
                )
                self.charge_scan(table, (acc_req,), row_count=chunk.rows)
                off = 0
                for start, n in chunk.segments:
                    pieces.append((start, tuple(o[off:off + n] for o in out)))
                    off += n
        pieces.sort(key=lambda p: p[0])
        if not pieces:  # a 0-row probe table owns no chunks on any shard
            return JoinResult(
                s_proj=jnp.zeros(0, jnp.int32),
                r_proj=jnp.zeros(0, jnp.int32),
                matched=jnp.zeros(0, bool),
            )
        return JoinResult.concat(
            [JoinResult(*self._to_root(t)) for _, t in pieces]
        )
