"""The Relational Memory Engine (RME) — host-side orchestration.

This module is the software incarnation of the paper's Fig. 5 datapath:

* ``register`` plays the **Configuration Port**: it writes the table geometry
  (row size R, row count N, enabled columns Q with widths/offsets, frame F)
  and returns an :class:`~repro.core.ephemeral.EphemeralView` handle.
* The **Reorganization Buffer** (data SPM + metadata SPM) becomes
  :class:`ReorgCache`: reorganized column groups keyed by geometry, validated
  by an *epoch*.  The paper invalidates the whole SPM in one cycle by bumping
  the RME epoch; we do exactly that — ``reset()`` is O(1), it never walks or
  frees entries eagerly.
* **Hot vs cold** accesses (paper Fig. 6) map to cache hit vs kernel launch.
  The engine counts both, plus exact bytes pulled from the row store, so the
  benchmarks report the same cache-efficiency story as the paper's PMU plots.

The engine's compute path is revision-selectable (``bsl``/``pck``/``mlp``
Pallas kernels, or the ``xla`` fused-gather path used when lowering for
non-TPU targets), mirroring the paper's §5.2 hardware revisions.

The write path: delta-chunked residency
---------------------------------------
In the paper, the row store lives next to the RME — it is never copied to get
scanned, and OLTP writes land in it directly.  The software analogue is
:class:`DeviceRowStore`, and since our 'DRAM' is host numpy, writes create a
host/device synchronization problem the store solves at **delta**
granularity:

* A table's device copy is a **base chunk plus appended tail chunks**
  (consecutive row ranges whose concatenation is the row store).  The first
  access uploads everything once; after that, an *append* of N rows ships
  exactly those N rows' words as a new tail chunk, and a *delete*/*update*
  ships exactly the patched hidden ``__ts_end`` words (replayed from the
  table's patch log) — never the whole table.  ``EngineStats`` splits the
  accounting: ``bytes_uploaded``/``uploads`` count every host→device
  transfer, ``bytes_uploaded_delta``/``delta_uploads`` the delta subset, so
  benchmarks can prove O(delta) transfer under sustained writes.
* The :class:`ReorgCache` is **delta-aware** for projections: a packed
  column group never contains the hidden timestamp words, so a cached view
  stays byte-valid for the physical rows it covers no matter how many
  deletes/updates patch timestamps.  A hot view whose table only grew is
  served by projecting just the appended tail and concatenating with the
  cached block (incremental view maintenance, counted in
  ``EngineStats.delta_hits``) instead of being invalidated.

Scan-sharing batch execution
----------------------------
Cold materializations and fused aggregates read the device-resident chunks —
repeated analytics over an unchanged table perform zero host→device
transfers.  On top sits :meth:`RelationalMemoryEngine.execute_many` (driven
by :class:`repro.core.executor.BatchExecutor` and the serving layer): pending
scan ops of **any** kind — projections, predicated filters, fused aggregates,
group-by partials, join probes (:mod:`repro.core.requests`) — are coalesced
per table,
lowered to kernel scan requests (equal requests de-duplicate into one output
slot), and served by the heterogeneous one-pass kernel in
``repro.kernels.rme_scan_multi``: one Fetch-Unit stream **per chunk** per
table per batch, every request's output emitted from those passes and
combined across chunks (blocked outputs concatenate, aggregate/group-by
partials add — see ``scan_multi_chunked``).  Bus-beat bytes are attributed
exactly once per chunk via the *union* geometry over all requests' enabled
words (:func:`repro.kernels.rme_scan_multi.union_geometry`), every projection
lands in the :class:`ReorgCache` so subsequent accesses are hot, and a batch
whose modeled VMEM working set exceeds the 2 MB SPM budget auto-halves its
row-tile height before launching (``EngineStats.last_block_rows`` records the
choice).  A lone request keeps its single-op kernel — solo queries never pay
the fused formulation.  :meth:`materialize_many` is the projection-only thin
wrapper, and ``aggregate_async`` — the non-blocking sibling of ``aggregate``
— is a one-op batch through the same path.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import common
from repro.kernels import ops as K
from repro.kernels import rme_scan_multi as KR
from repro.kernels.rme_project import vmem_footprint_bytes

from . import faults, trace
from .descriptor import bytes_moved
from .ephemeral import EphemeralView
from .requests import (AggregateOp, JoinOp, JoinResult, ProjectOp, ScanOp,
                       finalize_scan_result)
from .schema import WORD, TableGeometry
from .table import RelationalTable

# the fused-pass tile guard never shrinks below this (grid overhead dominates)
MIN_FUSED_BLOCK_ROWS = 32

# streamed projections never slice finer than this: below it the per-chunk
# launch overhead dwarfs the chunk itself and the bus-beat rounding per slice
# starts to distort the Eq.(3) accounting
MIN_STREAM_CHUNK_ROWS = 32

# tail chunks are coalesced (device-side, no host transfer) beyond this count
# so per-chunk pass overhead stays bounded under sustained appends
MAX_TAIL_CHUNKS = 8


@dataclasses.dataclass
class EngineStats:
    """Counters surfaced to the benchmarks (the 'PMU' of the software RME).

    Charging rules (the single source of truth the benchmarks rely on):

    * ``bytes_from_dram`` — bus-beat-exact Eq.(3) bytes a scan pulled from
      the row store (union geometry for shared passes, charged once per
      chunk per pass).
    * ``bytes_to_cpu`` — packed bytes shipped up the hierarchy (per view;
      scalar syncs charge their 8 bytes at the blocking call).
    * ``bytes_uploaded`` / ``uploads`` — every host→device row-store
      transfer (full uploads *and* deltas; one event per sync).
    * ``bytes_uploaded_delta`` / ``delta_uploads`` — the delta subset:
      appended tail rows and patched ``__ts_end`` words only.  An append of
      N rows to a resident T-row table charges O(N) here, never O(T).
    * ``delta_hits`` — reorg-cache entries served by an incremental
      tail-chunk projection (also counted in ``cold_misses``: a scan, albeit
      a small one, did run).
    * ``bytes_collective`` / ``collective_ops`` — modeled interconnect
      traffic of the sharded backend: cross-shard reduction combines
      (aggregate ``[sum, count]`` pairs, group-by ``(G, 2)`` partials) and
      join build-partition broadcasts.  Always O(result/build) bytes, never
      O(rows) — blocked outputs gather through ``bytes_to_cpu`` like any
      packed view.  Zero on the single-device backend.
    * ``bytes_saved_compression`` — bytes the §4 codecs kept *off* the bus:
      for every charged pass whose union geometry touches encoded columns,
      the plain-width Eq.(3) cost minus the narrow cost actually booked to
      ``bytes_from_dram`` (``charge_scan`` is the single charge point).
    * ``decodes`` / ``decode_cache_hits`` — client-visible decode events on
      packed results (``EphemeralView.column`` → :meth:`RelationalMemoryEngine.
      decode_column``): real dictionary/FOR decodes vs per-table-version
      cache hits.  The fused pass itself never decodes — these counters stay
      0 until someone *reads* an encoded packed output.
    * ``retries`` / ``failovers`` / ``bytes_failover`` — the reliability
      layer's recovery work (``docs/reliability.md``): transient-fault
      retries of a shard pass or collective combine, shard passes
      re-executed on the root device after retries were exhausted (or the
      shard was quarantined), and the row bytes those failover passes
      re-scanned.  All zero in a fault-free run — the ≤5% overhead gate in
      ``fig_fault_recovery`` relies on that.
    * ``kernel_fallbacks`` — serves a Pallas revision sent to the XLA
      fallback instead of its kernel: a failed kernel dispatch, a route the
      circuit breaker holds open, or a join probe whose plane arrays do
      not fit the chip's VMEM.  Zero whenever every request of a Pallas
      revision ran on its kernel; the ``xla`` revision never counts here.
    """

    hot_hits: int = 0
    cold_misses: int = 0
    shared_scans: int = 0  # batched multi-view passes over a row store
    subsumed_requests: int = 0  # requests served by slicing a covering scan
    rows_projected: int = 0
    bytes_from_dram: int = 0  # bus-beat-accurate bytes the engine pulled
    bytes_to_cpu: int = 0  # packed bytes shipped up the hierarchy
    bytes_uploaded: int = 0  # host→device row-store transfer bytes (all)
    uploads: int = 0  # host→device row-store transfer count (all)
    bytes_uploaded_delta: int = 0  # of bytes_uploaded: delta-only transfers
    delta_uploads: int = 0  # of uploads: delta-only transfer events
    delta_hits: int = 0  # cache entries served by tail-chunk delta scans
    last_block_rows: int = 0  # row-tile height the fused-pass VMEM guard chose
    join_builds: int = 0  # hash-partition builds (one per build-table version)
    bytes_join_build: int = 0  # of bytes_uploaded: partition-array uploads
    bytes_collective: int = 0  # interconnect bytes (sharded reductions/broadcasts)
    collective_ops: int = 0  # cross-shard combine/broadcast events
    retries: int = 0  # transient-fault retries (shard passes, combines)
    failovers: int = 0  # shard passes re-executed on the root device
    bytes_failover: int = 0  # row bytes re-scanned by failover passes
    bytes_saved_compression: int = 0  # plain-minus-narrow bytes codecs kept off the bus
    decodes: int = 0  # client-read decodes of encoded packed results
    decode_cache_hits: int = 0  # decode results served from the per-version cache
    kernel_fallbacks: int = 0  # Pallas serves sent to the XLA fallback

    def reset(self) -> None:
        self.hot_hits = 0
        self.cold_misses = 0
        self.shared_scans = 0
        self.subsumed_requests = 0
        self.rows_projected = 0
        self.bytes_from_dram = 0
        self.bytes_to_cpu = 0
        self.bytes_uploaded = 0
        self.uploads = 0
        self.bytes_uploaded_delta = 0
        self.delta_uploads = 0
        self.delta_hits = 0
        self.last_block_rows = 0
        self.join_builds = 0
        self.bytes_join_build = 0
        self.bytes_collective = 0
        self.collective_ops = 0
        self.retries = 0
        self.failovers = 0
        self.bytes_failover = 0
        self.bytes_saved_compression = 0
        self.decodes = 0
        self.decode_cache_hits = 0
        self.kernel_fallbacks = 0


@dataclasses.dataclass
class PassHandle:
    """One enqueued op batch: the named half of the launch/finalize split.

    ``execute_many`` itself never syncs with the host — every result it
    returns is a device value (or a lazy cache hit) — but callers that want
    to *overlap* work need that contract spelled out as an object they can
    hold while doing something else.  :meth:`RelationalMemoryEngine.
    execute_many_async` returns one of these; the pipelined QueryServer
    stashes it for tick N while tick N+1 drains, compiles, and launches.

    ``results`` is aligned with the submitted ops (same order, same per-op
    contracts as ``execute_many``).  ``block_until_ready()`` is the only
    blocking member — an explicit rendezvous for callers that want the
    device drained without pulling any result to the host.
    """

    results: list

    def block_until_ready(self) -> "PassHandle":
        for r in self.results:
            if isinstance(r, JoinResult):
                jax.block_until_ready((r.s_proj, r.r_proj, r.matched))
            elif r is not None:
                jax.block_until_ready(r)
        return self


class ReorgCache:
    """Epoch-validated cache of reorganized views (the two SPMs of Fig. 5).

    An entry is valid iff its stored epoch equals the cache's current epoch —
    the paper's single-cycle invalidation.  Entries also carry a caller-chosen
    version token; the engine stores each packed projection under the **row
    coverage** it was built from (``table.row_count`` at build time).  Packed
    projections never include the hidden MVCC timestamp words, so an entry
    stays byte-valid for the rows it covers across any number of
    deletes/updates — only appends extend a table past an entry's coverage,
    and then the engine *delta-serves* it (tail projection + concatenate, see
    :meth:`RelationalMemoryEngine.materialize`) instead of discarding it.
    """

    def __init__(self, capacity_bytes: int = 2 << 20):  # paper: 2 MB data SPM
        self.capacity_bytes = capacity_bytes
        self.epoch = 0
        self._entries: dict[tuple, tuple[int, object, jax.Array]] = {}
        self._bytes = 0

    def reset(self) -> None:
        """Single-cycle SPM invalidation: bump the epoch; entries expire lazily."""
        self.epoch += 1

    def peek(self, key: tuple, version) -> jax.Array | None:
        """Exact-version probe without side effects.

        The planner costs queries with this; there is deliberately no
        delete-on-mismatch accessor — under coverage tokens a version
        mismatch usually means *delta-servable*, not garbage, so destroying
        mismatched entries would silently turn incremental tail serves back
        into full cold scans.  Entries are reclaimed by ``put`` (overwrite /
        stale-epoch sweep / FIFO eviction) instead.
        """
        hit = self._entries.get(key)
        if hit is None:
            return None
        epoch, ver, arr = hit
        if epoch != self.epoch or ver != version:
            return None
        return arr

    def lookup(self, key: tuple) -> tuple[object, jax.Array] | None:
        """Epoch-valid entry *regardless of version*: ``(version, arr)``.

        This is the delta-serving probe: the engine compares the stored row
        coverage against the table's current watermark to decide between a
        full hot hit, an incremental tail serve, or a cold rebuild.  Like
        ``peek``, it never mutates cache state.
        """
        hit = self._entries.get(key)
        if hit is None:
            return None
        epoch, ver, arr = hit
        if epoch != self.epoch:
            return None
        return ver, arr

    def put(self, key: tuple, version, arr: jax.Array) -> None:
        nbytes = arr.size * arr.dtype.itemsize
        if nbytes > self.capacity_bytes:
            return  # larger than the SPM: streamed, never cached (paper §6 scaling)
        old = self._entries.pop(key, None)
        if old is not None:
            self._bytes -= old[2].size * old[2].dtype.itemsize
        # evict stale-epoch entries first, then FIFO until it fits
        for k in [k for k, (e, _, _) in self._entries.items() if e != self.epoch]:
            _, _, a = self._entries.pop(k)
            self._bytes -= a.size * a.dtype.itemsize
        while self._bytes + nbytes > self.capacity_bytes and self._entries:
            oldest = next(iter(self._entries))  # FIFO: evict the oldest insert
            _, _, a = self._entries.pop(oldest)
            self._bytes -= a.size * a.dtype.itemsize
        self._entries[key] = (self.epoch, version, arr)
        self._bytes += nbytes

    @property
    def occupancy_bytes(self) -> int:
        return self._bytes


@dataclasses.dataclass
class _StoreEntry:
    """One table's device residency: base + tail chunks and sync positions."""

    chunks: list[jax.Array]  # consecutive row ranges; concat == rows [0, rows)
    rows: int  # append watermark this copy has synced to
    patch_seq: int  # table.mutation_version this copy has replayed to


class DeviceRowStore:
    """Delta-chunked device-resident row-store buffers, keyed by ``table.uid``.

    The paper's row store sits beside the RME in DRAM; nothing ever copies it
    to scan it.  Our 'DRAM' is host numpy, so the first access to a table must
    ship its word buffer to the device — but only the first.  After that the
    copy is kept in sync *incrementally*:

    * appended rows upload as a new **tail chunk** (O(new rows) bytes),
    * deleted/updated rows replay the table's patch log, rewriting only the
      hidden ``__ts_end`` word of each touched row inside the resident
      chunks (O(touched rows) words),
    * nothing else ever re-crosses the host→device boundary.

    ``get`` coalesces the chunk list into one array (a device-side concat —
    no host transfer, so it charges nothing) for single-buffer consumers;
    ``chunks`` hands the list to the chunk-iterating fused scan.  With
    ``delta=False`` the store reverts to whole-table re-upload on any change
    — the pre-delta behavior, kept as the measurable baseline for
    ``benchmarks/fig_htap_ingest.py``.

    One buffer set is kept per table identity (``uid``, never recycled —
    unlike ``id()``), a weakref finalizer drops it when its table is garbage
    collected, and every transfer is charged to the engine's PMU
    (``bytes_uploaded``/``uploads`` always; ``bytes_uploaded_delta``/
    ``delta_uploads`` additionally for delta syncs).
    """

    def __init__(self, stats: EngineStats | None = None, delta: bool = True):
        self.stats = stats
        self.delta = delta
        self._buffers: dict[int, _StoreEntry] = {}
        self._finalized: set[int] = set()  # uids with a registered finalizer

    @staticmethod
    def _finalize_entry(store_ref: "weakref.ref[DeviceRowStore]", uid: int) -> None:
        store = store_ref()
        if store is not None:
            store._buffers.pop(uid, None)
            store._finalized.discard(uid)

    # ----------------------------------------------------------------- sync
    def _charge(self, nbytes: int, is_delta: bool) -> None:
        if self.stats is None or nbytes == 0:
            return
        self.stats.uploads += 1
        self.stats.bytes_uploaded += nbytes
        if is_delta:
            self.stats.delta_uploads += 1
            self.stats.bytes_uploaded_delta += nbytes

    def _full_upload(self, table: RelationalTable) -> _StoreEntry:
        faults.maybe_fault("upload", table=table.uid, delta=False)
        host = table.words()
        ent = _StoreEntry([jnp.asarray(host)], table.row_count,
                          table.mutation_version)
        if table.uid not in self._finalized:
            # dead tables must not pin device memory: evict with their owner.
            # The finalizer must hold the store weakly — a strong reference
            # (e.g. the bound `self._buffers.pop`) would let any long-lived
            # table pin a dead engine's whole buffer set.  One finalizer per
            # uid: clear()/drop() + re-upload must not accumulate more.
            weakref.finalize(table, self._finalize_entry, weakref.ref(self), table.uid)
            self._finalized.add(table.uid)
        self._buffers[table.uid] = ent
        self._charge(host.size * host.itemsize, is_delta=False)
        return ent

    def _apply_patches(self, ent: _StoreEntry, table: RelationalTable,
                       patches: list[np.ndarray]) -> int:
        """Rewrite patched ``__ts_end`` words inside the resident chunks.

        Only rows below the entry's pre-sync watermark need patching — rows
        at or above it arrive in the freshly uploaded tail chunk with their
        current timestamps already in place.  Returns the bytes shipped.
        """
        idx = np.concatenate([p[p < ent.rows] for p in patches]) if patches else \
            np.empty(0, dtype=np.int64)
        if idx.size == 0:
            return 0
        vals = np.asarray(table.ts_end_at(idx))
        ts_word = table.ts_end_word
        start = 0
        for c, chunk in enumerate(ent.chunks):
            end = start + chunk.shape[0]
            sel = (idx >= start) & (idx < end)
            if sel.any():
                ent.chunks[c] = chunk.at[
                    jnp.asarray(idx[sel] - start), ts_word
                ].set(jnp.asarray(vals[sel]))
            start = end
        return idx.size * WORD  # one rewritten timestamp word per row

    def _sync(self, table: RelationalTable) -> _StoreEntry:
        """Bring the table's device copy current, shipping only the delta."""
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
            # before any entry mutation: a fault here leaves the resident
            # copy at its pre-sync state, so a bare retry re-syncs cleanly
            faults.maybe_fault("upload", table=table.uid, delta=True)
        moved = self._apply_patches(ent, table, patches)
        ent.patch_seq = table.mutation_version
        if table.row_count > ent.rows:
            tail = table.tail_words(ent.rows)
            ent.chunks.append(jnp.asarray(tail))
            ent.rows = table.row_count
            moved += tail.size * tail.itemsize
        self._charge(moved, is_delta=True)
        if len(ent.chunks) > MAX_TAIL_CHUNKS:
            # device-side compaction: no host transfer, nothing charged
            ent.chunks = [jnp.concatenate(ent.chunks, axis=0)]
        return ent

    # ------------------------------------------------------------ accessors
    def get(self, table: RelationalTable) -> jax.Array:
        """The table's row store as **one** device array (synced first).

        Multi-chunk entries are coalesced device-side and kept coalesced —
        single-buffer consumers (solo kernels, host fallbacks, validity
        masks) see exactly the pre-chunking contract.
        """
        ent = self._sync(table)
        if len(ent.chunks) > 1:
            ent.chunks = [jnp.concatenate(ent.chunks, axis=0)]
        return ent.chunks[0]

    def chunks(self, table: RelationalTable) -> tuple[jax.Array, ...]:
        """The table's resident chunk list (synced first), for per-chunk scans."""
        return tuple(self._sync(table).chunks)

    def tail(self, table: RelationalTable, start_row: int) -> jax.Array:
        """Device rows ``[start_row, row_count)`` — the delta-scan operand for
        incrementally maintained views.  Assembled by slicing the resident
        chunks (device-side; the sync itself shipped only the delta)."""
        ent = self._sync(table)
        parts, start = [], 0
        for chunk in ent.chunks:
            end = start + chunk.shape[0]
            if end > start_row:
                parts.append(chunk[max(start_row - start, 0) :])
            start = end
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    def contains(self, table: RelationalTable) -> bool:
        """True iff the resident copy is fully current (no pending delta)."""
        ent = self._buffers.get(table.uid)
        return (ent is not None and ent.rows == table.row_count
                and ent.patch_seq == table.mutation_version)

    def drop(self, table: RelationalTable) -> None:
        self._buffers.pop(table.uid, None)

    def clear(self) -> None:
        self._buffers.clear()

    @property
    def occupancy_bytes(self) -> int:
        return sum(
            c.size * c.dtype.itemsize
            for ent in self._buffers.values() for c in ent.chunks
        )


# -------------------------------------------------- kernel-call row ranges
def _row_widths(words: jax.Array, reqs: Sequence["KR.ScanRequest"]) -> list[int]:
    """Word width of every row-indexed operand and output of one scan call."""
    widths = [words.shape[1]]
    for req in reqs:
        if isinstance(req, (KR.ProjectRequest, KR.FilterRequest)):
            widths.append(req.geom.out_words_per_row)
        if isinstance(req, KR.FilterRequest):
            widths.append(1)  # the row mask
    return widths


def _row_pieces(words: jax.Array, limit: int | None) -> list[jax.Array]:
    """``words`` cut into row ranges of at most ``limit`` rows.  Rows are
    position-local, so per-piece kernel outputs combine like per-chunk ones."""
    n = words.shape[0]
    if limit is None or n <= limit:
        return [words]
    pieces = []
    for i, start in enumerate(range(0, n, limit)):
        rows = min(limit, n - start)
        with trace.span("engine.row_slice", range=i, rows=rows):
            pieces.append(jax.lax.dynamic_slice_in_dim(words, start, rows))
    return pieces


# -------------------------------------------------- request subsumption
def _geom_words(geom) -> tuple[int, ...]:
    """The absolute row-word indices a geometry enables, packed order."""
    words: list[int] = []
    for off, width in zip(geom.abs_offsets, geom.col_widths):
        words.extend(range(off // WORD, (off + width) // WORD))
    return tuple(words)


def _request_width(req: "KR.ScanRequest") -> int:
    """Covering-candidate ordering key: widest projections become the
    representatives, so subset requests fold into them."""
    if isinstance(req, (KR.ProjectRequest, KR.FilterRequest)):
        return len(_geom_words(req.geom))
    return -1  # aggregate/group-by requests never cover packed outputs


def _request_covers(a: "KR.ScanRequest", b: "KR.ScanRequest") -> bool:
    """Does serving ``a`` let the engine derive ``b``'s output exactly?

    The subsumption rule of the tick batcher: ``a``'s enabled words must be
    a superset of ``b``'s (projection ⊇) and ``a``'s predicate must be
    weaker-or-equal (predicate ⊆ in selected rows), so every row ``b``
    keeps is intact in ``a``'s packed output.  Derivation slices ``b``'s
    words out of ``a``'s packed block and, for filters, re-evaluates ``b``'s
    predicate on the raw packed words (code space — decode-free).
    Aggregate/group-by outputs are scalars/partials and take no part.
    """
    if not isinstance(a, (KR.ProjectRequest, KR.FilterRequest)):
        return False
    if not isinstance(b, (KR.ProjectRequest, KR.FilterRequest)):
        return False
    aw = set(_geom_words(a.geom))
    if isinstance(b, KR.ProjectRequest):
        # a filter's packed output zeroes failing rows — never a pure project
        return isinstance(a, KR.ProjectRequest) and aw >= set(_geom_words(b.geom))
    need = set(_geom_words(b.geom))
    if b.pred_op != "none":
        need.add(b.pred_word)
    if isinstance(a, KR.ProjectRequest):
        # visibility lives in ts words the packed block does not carry
        return b.ts_word < 0 and aw >= need
    if (a.ts_word, a.ts) != (b.ts_word, b.ts):
        return False
    weaker = a.pred_op == "none" or (
        a.pred_word == b.pred_word
        and a.pred_dtype == b.pred_dtype
        and a.pred_op == b.pred_op
        and (a.pred_k <= b.pred_k if a.pred_op == "gt" else a.pred_k >= b.pred_k)
    )
    return weaker and aw >= need


def _cover_requests(
    reqs: tuple["KR.ScanRequest", ...],
) -> tuple[tuple["KR.ScanRequest", ...], dict]:
    """Greedy covering: (representatives in input order, covered→rep map)."""
    cover: dict = {}
    reps: list = []
    for req in sorted(reqs, key=_request_width, reverse=True):
        rep = next((r for r in reps if _request_covers(r, req)), None)
        if rep is not None:
            cover[req] = rep
        else:
            reps.append(req)
    return tuple(r for r in reqs if r not in cover), cover


class RelationalMemoryEngine:
    """Host-side RME: registers ephemeral views and materializes them on access.

    ``revision`` selects the datapath (paper §5.2): ``"bsl"``, ``"pck"``,
    ``"mlp"`` (Pallas kernels), or ``"xla"`` (fused gather).  ``interpret``
    defaults to the backend: Mosaic-compiled kernels on a TPU, the Pallas
    interpreter anywhere else (``engine.interpret`` records the choice).
    ``vmem_bytes`` is the VMEM budget the fused pass and the join probe size
    their row tiles against in interpret mode; compiled kernels size against
    the chip's own VMEM instead (:meth:`_vmem_budget`).
    ``delta_uploads=False`` disables the whole write-path delta machinery:
    any table change re-ships the full device buffer on next access, and a
    grown table turns cached views cold instead of delta-serving them — the
    measurable pre-delta baseline the HTAP ingest benchmark compares against.
    """

    def __init__(
        self,
        revision: str = "mlp",
        block_rows: int = K.DEFAULT_BLOCK_ROWS,
        cache_bytes: int = 2 << 20,
        interpret: bool | None = None,
        vmem_bytes: int = 2 << 20,  # paper: 2 MB data SPM
        delta_uploads: bool = True,
        breaker_threshold: int = 3,
        breaker_cooldown: int = 4,
        subsume: bool = True,
    ):
        if revision not in K.REVISIONS:
            raise ValueError(f"unknown revision {revision!r}; want one of {K.REVISIONS}")
        self.revision = revision
        self.block_rows = block_rows
        self.interpret = common.resolve_interpret(interpret)
        self.vmem_bytes = vmem_bytes
        self._chip: tuple[int, int | None] | None = None  # see _chip_limits
        self.delta = delta_uploads
        # subsumption-aware sharing: a batch member whose projection ⊆ and
        # predicate ⊇ another's is served by slicing/masking the covering
        # request's output instead of its own slot in the fused pass
        self.subsume = subsume
        self.cache = ReorgCache(cache_bytes)
        self.stats = EngineStats()
        self.rowstore = DeviceRowStore(self.stats, delta=delta_uploads)
        # decode-on-finalize cache: decoded client reads of encoded packed
        # outputs, keyed per table version/storage epoch (FIFO-capped)
        self._decode_cache: dict[tuple, object] = {}
        # lowering circuit breaker: flips a repeatedly-failing (table,
        # request-shape) route to the XLA fallback (docs/reliability.md)
        self.breaker = faults.CircuitBreaker(
            threshold=breaker_threshold, cooldown=breaker_cooldown
        )

    @property
    def backend(self) -> str:
        """Execution-backend identity: ``"single"`` here, ``"sharded"`` on
        :class:`repro.core.distributed.ShardedEngine`.  The planner's
        ``compile_plan(..., backend=...)`` validates against this — routing
        itself is dynamic dispatch (the sharded engine overrides the scan
        and join serving hooks), so a compiled plan runs on whichever
        backend its engine is."""
        return "single"

    # ---------------------------------------------------------------- config
    def register(
        self,
        table: RelationalTable,
        columns: Sequence[str],
        snapshot_ts: int | None = None,
        frame: int = 0,
    ) -> EphemeralView:
        """Configuration-port write: define a column-group view over ``table``.

        Nothing is materialized here (ephemeral variables "are never
        instantiated in the main memory"); the returned view triggers the
        engine on first access.  ``snapshot_ts`` pins the view's MVCC
        visibility: decoded accesses (``view.column``) and fused ops built
        from the view see exactly the rows live at that time, no matter what
        writes land afterwards — the packed block itself always covers every
        physical row (visibility is a mask, not a rewrite).
        """
        geom = TableGeometry.from_schema(
            table.schema, columns, row_count=table.row_count, frame=frame
        )
        return EphemeralView(self, table, tuple(columns), geom, snapshot_ts)

    def reset(self) -> None:
        """The configuration port's software reset SW (Table 1).

        Clears every derived-data cache the reset must invalidate: the reorg
        cache (epoch bump, O(1)) *and* the module-global q5 build-index cache
        — that one is keyed by table version, not engine epoch, so without an
        explicit clear its sorted indexes and ``JOIN_BUILD_STATS`` leak across
        benchmark repetitions.  (The cache is process-global, like the paper's
        single RME: resetting any engine resets it.)  The device row store is
        *not* dropped — it mirrors the row store itself, not derived state.
        """
        self.cache.reset()
        from .planner import clear_join_build_cache  # deferred: planner imports us

        clear_join_build_cache()

    # --------------------------------------------------------------- engine
    def view_key(self, table: RelationalTable, geom: TableGeometry) -> tuple:
        """The reorg-cache key for a view — the single definition every
        consumer (materialization, planner costing, serving-layer hot/cold
        classification) must agree on.  Keyed by the column *layout* only
        (row count excluded): a view over a grown table shares its slot with
        the pre-growth entry, which is what makes delta serving possible —
        the entry's stored version records the rows it covers.  The table's
        ``storage_epoch`` is folded in: a codec re-fit rewrites stored code
        words in place, so every pre-refit packed block is garbage."""
        return (table.uid, geom.layout_key(), self.revision,
                getattr(table, "storage_epoch", 0))

    def peek_project(self, table: RelationalTable,
                     geom: TableGeometry) -> jax.Array | None:
        """Side-effect-free full-hot probe for planner/server costing: the
        cached packed block iff it covers every current row."""
        return self.cache.peek(self.view_key(table, geom), table.row_count)

    def projection_is_cached(self, table: RelationalTable,
                             geom: TableGeometry) -> bool:
        """Side-effect-free: will :meth:`_project_from_cache` serve this view
        without a full scan — either a full hot hit or (in delta mode) a
        tail-only delta serve?  The serving layer uses this to keep its
        shared-scan/bytes-saved accounting aligned with what ``execute_many``
        will actually do."""
        ent = self.cache.lookup(self.view_key(table, geom))
        if ent is None:
            return False
        rows_cached = ent[0]
        if rows_cached == table.row_count:
            return True
        return (self.delta and isinstance(rows_cached, int)
                and 0 < rows_cached < table.row_count)

    def device_words(self, table: RelationalTable) -> jax.Array:
        """The table's device-resident word buffer as one array.

        The underlying sync ships only the write delta (appended rows,
        patched timestamp words) since the last access; multi-chunk entries
        are coalesced device-side.
        """
        return self.rowstore.get(table)

    def device_chunks(self, table: RelationalTable) -> tuple[jax.Array, ...]:
        """The table's resident base+tail chunk list (synced, O(delta))."""
        return self.rowstore.chunks(table)

    def valid_mask(self, table: RelationalTable, ts: int) -> jax.Array:
        """MVCC row visibility at snapshot ``ts``, from the device-resident
        hidden timestamp words: ``ts_begin <= ts < ts_end``.  The single
        host-side spelling of the visibility rule — ephemeral views and the
        planner's fallback routes both use it; the fused kernels evaluate
        the same test in-scan.  The underlying sync ships only the write
        delta, so this is O(patched rows) fresh after any number of writes.
        """
        words = self.device_words(table)
        begin = words[:, table.ts_begin_word]
        end = words[:, table.ts_end_word]
        return (begin <= ts) & (ts < end)

    def _project_from_cache(
        self, table: RelationalTable, geom: TableGeometry
    ) -> jax.Array | None:
        """Serve a projection from the reorg cache: full hot hit, or an
        incremental tail scan over the appended rows merged with the cached
        block (delta serve).  Returns ``None`` when a cold rebuild is needed.

        Correctness note: packed projections contain only user-column words,
        so deletes/updates (which rewrite hidden ``__ts_end`` words) never
        stale an entry — visibility is applied downstream by whoever masks
        (``EphemeralView.column``, fused snapshot tests).  Coverage is the
        only axis: an entry built at watermark ``w`` is byte-exact for rows
        ``[0, w)`` forever.
        """
        ent = self.cache.lookup(self.view_key(table, geom))
        if ent is None:
            return None
        rows_cached, cached = ent
        if rows_cached == table.row_count:
            self.stats.hot_hits += 1
            return cached
        if not self.delta:  # pre-delta compatibility mode: growth = cold
            return None
        if not isinstance(rows_cached, int) or not 0 < rows_cached < table.row_count:
            return None
        # incremental view maintenance: project only the appended tail
        n_tail = table.row_count - rows_cached
        tail = self.rowstore.tail(table, rows_cached)
        tail_geom = dataclasses.replace(geom, row_count=n_tail)
        packed_tail = K.project_any(
            tail, tail_geom, revision=self.revision,
            block_rows=self.block_rows, interpret=self.interpret,
        )
        packed = jnp.concatenate([cached, packed_tail], axis=0)
        self.stats.delta_hits += 1
        self.stats.cold_misses += 1  # a (tail-sized) scan did run
        moved = bytes_moved(tail_geom)
        self.stats.rows_projected += n_tail
        self.stats.bytes_from_dram += moved["rme"]
        self.stats.bytes_to_cpu += moved["columnar"]
        self.cache.put(self.view_key(table, geom), table.row_count, packed)
        return packed

    def materialize(self, view: EphemeralView) -> jax.Array:
        """Assemble the packed column group for ``view``: hot out of the
        reorganization cache, incrementally from a cached block plus a
        tail-chunk delta scan when the table only grew, or cold through the
        projection kernel."""
        table, geom = view.table, view.geometry
        served = self._project_from_cache(table, geom)
        if served is not None:
            return served
        self.stats.cold_misses += 1
        words = self.device_words(table)
        packed = K.project_any(
            words, geom, revision=self.revision, block_rows=self.block_rows,
            interpret=self.interpret,
        )
        moved = bytes_moved(geom)
        self.stats.rows_projected += geom.row_count
        self.stats.bytes_from_dram += moved["rme"]
        self.stats.bytes_to_cpu += moved["columnar"]
        self.cache.put(self.view_key(table, geom), table.row_count, packed)
        return packed

    def stream_project(self, view: EphemeralView,
                       chunk_rows: int | None = None):
        """Generator: the view's packed projection, one chunk at a time.

        The streaming sibling of :meth:`materialize` — instead of one packed
        block (and one blocking transfer for the consumer), the projection is
        emitted incrementally per **resident chunk** of the delta-chunked
        device row store, so a consumer (the QueryServer's streaming tickets)
        can forward each piece as soon as its scan lands.  ``chunk_rows``
        optionally re-slices resident chunks into at-most-that-many-row
        pieces (never below ``MIN_STREAM_CHUNK_ROWS``): a never-appended
        table is a single base chunk, and a bounded slice is what gives a
        multi-megabyte output its incremental delivery.

        Charging is per emitted chunk, with the same rules as a cold
        materialization of that many rows: ``rows_projected``, Eq.(3)
        ``bytes_from_dram`` over the sliced geometry, and packed
        ``bytes_to_cpu`` — each charged when its chunk is yielded, so an
        abandoned stream charges only what it actually moved.  A view the
        reorg cache can serve (hot hit or delta serve) arrives as one free
        chunk; a cold stream's concatenation lands in the cache after the
        last chunk, exactly like :meth:`materialize`.  The sharded backend
        streams unchanged: :meth:`device_chunks` there returns the per-shard
        parts in global row order.

        The *call* snapshots the resident chunk list eagerly (and triggers
        any needed upload); only the per-chunk scans are lazy.  This is what
        makes streams safe under pipelined serving — writes applied after
        the call (e.g. by the next tick's ``begin_tick``) cannot leak into
        a stream that was launched against the previous tick's state.
        """
        table, geom = view.table, view.geometry
        served = self._project_from_cache(table, geom)
        if served is not None:
            return iter((served,))
        self.stats.cold_misses += 1
        if chunk_rows is not None:
            chunk_rows = max(int(chunk_rows), MIN_STREAM_CHUNK_ROWS)
        chunks = tuple(self.device_chunks(table))
        return self._stream_chunks(table, geom, chunks, chunk_rows,
                                   table.row_count)

    def _stream_chunks(self, table: RelationalTable, geom, chunks,
                       chunk_rows: int | None, row_count: int):
        """The lazy half of :meth:`stream_project`: scan + charge + yield
        per chunk, then cache the concatenation under the snapshotted
        ``row_count`` (not the table's current one — the table may have
        grown while the stream drained)."""
        parts = []
        for chunk in chunks:
            start = 0
            while start < chunk.shape[0]:
                faults.maybe_fault("stream_chunk", table=table.uid,
                                   index=len(parts))
                stop = (chunk.shape[0] if chunk_rows is None
                        else min(start + chunk_rows, chunk.shape[0]))
                piece = chunk[start:stop]
                start = stop
                cg = dataclasses.replace(geom, row_count=piece.shape[0])
                packed = K.project_any(
                    piece, cg, revision=self.revision,
                    block_rows=self.block_rows, interpret=self.interpret,
                )
                moved = bytes_moved(cg)
                self.stats.rows_projected += cg.row_count
                self.stats.bytes_from_dram += moved["rme"]
                self.stats.bytes_to_cpu += moved["columnar"]
                parts.append(packed)
                yield packed
        if parts:
            full = parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)
            self.cache.put(self.view_key(table, geom), row_count, full)

    def execute_many(self, ops: Sequence[ScanOp]) -> list:
        """Serve a heterogeneous op batch with one shared scan per table.

        Any mix of :class:`~repro.core.requests.ProjectOp` /
        ``FilterOp`` / ``AggregateOp`` / ``GroupByOp`` is coalesced per table:
        each table's cold work is lowered to kernel scan requests
        (de-duplicated — equal requests share one output slot) and served by
        the heterogeneous one-pass kernel (``rme_scan_multi``) streamed over
        the table's **resident chunk list** — blocked outputs concatenate
        across chunks, aggregate/group-by partials add — with bus-beat bytes
        charged once per chunk via the union geometry over every request's
        enabled words.  A lone request keeps today's single-op kernel
        (``project``/``filter_project``/``aggregate``/``groupby_sum`` — the
        bsl/pck revisions stay exercised and nothing retraces).  Hot
        projections are served from the reorganization cache (including
        delta serves over appended tails), and every cold projection lands
        there, warming the SPM for all batch members.  When the fused pass's
        modeled VMEM working set exceeds the engine's SPM budget, the
        row-tile height is halved (down to ``MIN_FUSED_BLOCK_ROWS``) before
        launching; the chosen tile is exposed as
        ``EngineStats.last_block_rows``.  Results are returned in input
        order, each matching its op's single-op contract.
        """
        results: list = [None] * len(ops)
        pending: dict[int, list[tuple[int, KR.ScanRequest]]] = {}
        tables: dict[int, RelationalTable] = {}
        for i, op in enumerate(ops):
            if isinstance(op, ProjectOp):
                served = self._project_from_cache(op.table, op.view.geometry)
                if served is not None:
                    results[i] = served
                    continue
            pending.setdefault(op.table.uid, []).append((i, op.lower()))
            tables[op.table.uid] = op.table
        for tid, entries in pending.items():
            table = tables[tid]
            uniq = dict.fromkeys(req for _, req in entries)
            reqs = tuple(uniq)
            self.stats.cold_misses += len(entries)
            if (len(entries) == 1 and isinstance(ops[entries[0][0]], JoinOp)
                    and ops[entries[0][0]].pred_op == "none"):
                # a join alone on its table skips the packed materialization:
                # the probe kernel streams the row-store chunks directly, and
                # nothing crosses toward the CPU but the join result (a
                # probe-side predicate needs the filtered packed route below)
                with trace.span("engine.join_direct", table=tid):
                    results[entries[0][0]] = self._join_direct(
                        ops[entries[0][0]])
                continue
            cover: dict = {}
            if self.subsume and len(reqs) > 1:
                # subsumption-aware sharing: a request whose words ⊆ and
                # predicate ⊇ a covering request's is served by deriving
                # from the covering output, not by its own fused slot
                reqs, cover = _cover_requests(reqs)
            with trace.span("engine.serve_scan", table=tid,
                            requests=len(reqs)):
                outs = self._serve_scan(table, reqs, shared=bool(cover))
            by_req = dict(zip(reqs, outs))
            for req, rep in cover.items():
                with trace.span("engine.derive_covered", table=tid):
                    by_req[req] = self._derive_covered(
                        rep, req, by_req[rep])
            self.stats.subsumed_requests += len(cover)
            # a packed block consumed only by join probes stays on device —
            # bytes_to_cpu is charged only when a non-join consumer ships it
            cpu_reqs = {req for i, req in entries
                        if not isinstance(ops[i], JoinOp)}
            for req, out in by_req.items():
                if isinstance(req, KR.ProjectRequest):
                    geom = req.geom
                    if req in cpu_reqs:
                        self.stats.bytes_to_cpu += (
                            geom.row_count * geom.out_bytes_per_row
                        )
                    self.cache.put(
                        self.view_key(table, geom), table.row_count, out
                    )
            for i, req in entries:
                out = by_req[req]
                if isinstance(ops[i], JoinOp):
                    with trace.span("engine.finish_join", table=tid):
                        results[i] = self._finish_join(ops[i], out)
                else:
                    results[i] = finalize_scan_result(ops[i], out)
        return results

    def execute_many_async(self, ops: Sequence[ScanOp], *,
                           tick: int | None = None) -> PassHandle:
        """:meth:`execute_many` wrapped in a :class:`PassHandle`.

        Identical serving and accounting — one heterogeneous shared pass per
        table, results in op order — but the return type states the async
        contract explicitly: nothing has synced with the host, and the
        caller may hold the handle across arbitrary host work (the pipelined
        serving tick compiles and launches tick N+1 while tick N's handle is
        outstanding).  Works unchanged on the sharded backend, whose
        per-shard passes also enqueue without host syncs.  The pass is the
        span ``engine.execute_many``; ``tick`` labels it with the serving
        tick that sent it.
        """
        with trace.span("engine.execute_many", tick=tick, ops=len(ops)):
            return PassHandle(self.execute_many(ops))

    def materialize_many(self, views: Sequence[EphemeralView]) -> list[jax.Array]:
        """Materialize a batch of views with one shared scan per table.

        Thin wrapper over :meth:`execute_many`: each view becomes a
        :class:`~repro.core.requests.ProjectOp`, so a multi-view batch rides
        the heterogeneous one-pass scan (bus-beat bytes charged once via the
        union geometry) and every result lands in the reorganization cache.
        Results are returned in input order.
        """
        return self.execute_many([ProjectOp(v) for v in views])

    # -------------------------------------------- fused one-pass internals
    def _serve_scan(self, table: RelationalTable,
                    reqs: tuple["KR.ScanRequest", ...],
                    shared: bool = False) -> list:
        """Serve one table's de-duplicated request tuple — the backend hook.

        Single-device: a lone request stays on its single-op kernel (keeps
        the bsl/pck revision kernels exercised, doesn't count a shared
        scan); two or more fuse into one heterogeneous pass streamed over
        the resident chunk list.  ``shared=True`` forces the fused path for
        a lone request too — how a subsumption-collapsed batch keeps the
        union-geometry charging and ``shared_scans`` accounting of the
        multi-consumer pass it replaces.  The sharded backend overrides this
        with one fused pass per shard plus reduction-only cross-shard
        combines — requests are chunk-agnostic (word offsets,
        row-position-local), so the same lowered tuple serves both backends
        unchanged.
        """
        faults.maybe_fault("scan_launch", table=table.uid)
        if len(reqs) == 1 and not shared:
            words = self.device_words(table)
            return [self._execute_solo(words, table, reqs[0])]
        chunks = self.device_chunks(table)
        vmem = self._vmem_budget(chunks[0])
        block_rows = self._fused_block_rows(reqs, table.row_words, vmem)
        route = (table.uid, tuple(KR._strip_dynamic(r) for r in reqs))
        # a chunk larger than one kernel call may take is scanned in row
        # ranges, whose outputs combine like the chunks'
        per_chunk = [
            out for c, chunk in enumerate(chunks)
            for out in self._scan_ranges(c, chunk, reqs, block_rows, vmem,
                                         route)
        ]
        if len(per_chunk) == 1:
            outs = per_chunk[0]
        else:
            with trace.span("engine.combine", requests=len(reqs),
                            ranges=len(per_chunk)):
                outs = [KR.combine_chunk_outputs(req, [o[r] for o in per_chunk])
                        for r, req in enumerate(reqs)]
        self.stats.shared_scans += 1
        self.stats.rows_projected += table.row_count
        for chunk in chunks:
            self.charge_scan(table, reqs, row_count=chunk.shape[0])
        return outs

    def _scan_ranges(self, c: int, chunk: jax.Array,
                     reqs: tuple["KR.ScanRequest", ...], block_rows: int,
                     vmem: int, route) -> list:
        """Chunk ``c``'s fused pass, one kernel call (and span) per row
        range.  The range slices die when this returns, before the caller
        combines the outputs."""
        outs = []
        for i, piece in enumerate(_row_pieces(chunk, self._kernel_row_limit(
                chunk, _row_widths(chunk, reqs), block_rows))):
            with trace.span("engine.scan_multi", chunk=c, range=i,
                            rows=piece.shape[0]):
                outs.append(self._scan_chunk(piece, reqs, block_rows, vmem,
                                             route))
        return outs

    def _scan_chunk(self, chunk: jax.Array,
                    reqs: tuple["KR.ScanRequest", ...], block_rows: int,
                    vmem: int, route) -> list:
        """One chunk's fused pass behind the lowering circuit breaker.

        A ``closed`` route attempts the Pallas pass; a failure (a real
        lowering error or an injected ``lowering`` fault) records against
        the route and this chunk is served by the fused-gather XLA fallback
        — same results, per the xla-revision equality suite.  An ``open``
        route skips the attempt entirely for the cooldown.  Injected faults
        belonging to *other* sites propagate untouched: the breaker guards
        kernel dispatch, not the pass as a whole.
        """
        if self.revision == "xla":
            return KR.scan_multi_xla(chunk, tuple(reqs))
        if not self.breaker.allow(route):
            self.stats.kernel_fallbacks += 1
            return KR.scan_multi_xla(chunk, tuple(reqs))
        try:
            faults.maybe_fault("lowering", op="scan")
            outs = KR.scan_multi(
                chunk, reqs, revision=self.revision,
                block_rows=block_rows, interpret=self.interpret,
                vmem_limit=vmem,
            )
        except Exception as err:
            if isinstance(err, faults.FaultError) and err.site != "lowering":
                raise
            self.breaker.record_failure(route)
            self.stats.kernel_fallbacks += 1
            return KR.scan_multi_xla(chunk, tuple(reqs))
        self.breaker.record_success(route)
        return outs

    def _execute_solo(self, words: jax.Array, table: RelationalTable,
                      req: "KR.ScanRequest"):
        """One request: accounting here, kernel dispatch behind the breaker
        in :meth:`_solo_kernel` (failures fall back to ``scan_multi_xla``,
        which honors every single-op contract)."""
        if isinstance(req, KR.ProjectRequest):
            self.stats.rows_projected += req.geom.row_count
            self.stats.bytes_from_dram += bytes_moved(req.geom)["rme"]
        else:
            self.stats.rows_projected += table.row_count
            self.charge_scan(table, (req,))
        if words.shape[0] == 0:
            # the single-op Pallas kernels need at least one row block; an
            # empty resident store short-circuits to the XLA reference pass
            return KR.scan_multi_xla(words, (req,))[0]
        if self.revision == "xla":
            return self._solo_kernel(words, req)
        route = (table.uid, (KR._strip_dynamic(req),))
        if not self.breaker.allow(route):
            self.stats.kernel_fallbacks += 1
            return KR.scan_multi_xla(words, (req,))[0]
        pieces = _row_pieces(words, self._kernel_row_limit(
            words, _row_widths(words, (req,)), self.block_rows))
        try:
            faults.maybe_fault("lowering", op="scan")
            outs = []
            for i, piece in enumerate(pieces):
                with trace.span("engine.scan_solo", range=i,
                                rows=piece.shape[0]):
                    outs.append(self._solo_kernel(piece, req))
        except Exception as err:
            if isinstance(err, faults.FaultError) and err.site != "lowering":
                raise
            self.breaker.record_failure(route)
            self.stats.kernel_fallbacks += 1
            return KR.scan_multi_xla(words, (req,))[0]
        self.breaker.record_success(route)
        if len(outs) == 1:
            return outs[0]
        with trace.span("engine.combine", requests=1, ranges=len(outs)):
            return KR.combine_chunk_outputs(req, outs)

    def _solo_kernel(self, words: jax.Array, req: "KR.ScanRequest"):
        """Single-op kernel dispatch (bsl/pck revisions stay exercised)."""
        if isinstance(req, KR.ProjectRequest):
            return K.project_any(
                words, req.geom, revision=self.revision,
                block_rows=self.block_rows, interpret=self.interpret,
            )
        if isinstance(req, KR.FilterRequest):
            return K.filter_project(
                words, req.geom, pred_word=req.pred_word,
                pred_dtype=req.pred_dtype, pred_op=req.pred_op,
                pred_k=req.pred_k, ts=req.ts, ts_word=req.ts_word,
                block_rows=self.block_rows, interpret=self.interpret,
            )
        if isinstance(req, KR.AggregateRequest):
            return K.aggregate(
                words, agg_word=req.agg_word, agg_dtype=req.agg_dtype,
                pred_word=req.pred_word, pred_dtype=req.pred_dtype,
                pred_op=req.pred_op, pred_k=req.pred_k, ts=req.ts,
                ts_word=req.ts_word, block_rows=self.block_rows,
                interpret=self.interpret,
            )
        return K.groupby_sum(
            words, group_word=req.group_word, agg_word=req.agg_word,
            num_groups=req.num_groups, agg_dtype=req.agg_dtype,
            pred_word=req.pred_word, pred_dtype=req.pred_dtype,
            pred_op=req.pred_op, pred_k=req.pred_k, ts=req.ts,
            ts_word=req.ts_word, block_rows=self.block_rows,
            interpret=self.interpret,
        )

    def _derive_covered(self, covering: "KR.ScanRequest",
                        covered: "KR.ScanRequest", out):
        """Finalize a subsumed request from its covering request's output.

        Pure word-slicing on device: the covering packed block holds every
        word ``covered`` enables, so its output is a static column gather —
        and a covered filter re-evaluates its (already code-space) predicate
        on the raw packed words, exactly what the fused kernel would have
        computed.  No row-store pass, no decode.
        """
        geom = covering.geom
        word_out: dict[int, int] = {}
        for off, width in zip(geom.abs_offsets, geom.col_widths):
            for j in range(width // WORD):
                word_out[off // WORD + j] = len(word_out)
        packed, mask = (out if isinstance(covering, KR.FilterRequest)
                        else (out, None))
        idx = jnp.asarray(
            [word_out[w] for w in _geom_words(covered.geom)], jnp.int32
        )
        sliced = packed[:, idx]
        if isinstance(covered, KR.ProjectRequest):
            return sliced
        if covered.pred_op != "none":
            vals = common.decode(packed[:, word_out[covered.pred_word]],
                                 covered.pred_dtype)
            k = jnp.asarray(
                covered.pred_k,
                jnp.float32 if covered.pred_dtype == "float32" else jnp.int32,
            )
            m = vals > k if covered.pred_op == "gt" else vals < k
        else:
            m = jnp.ones(sliced.shape[0], bool)
        if mask is not None:
            m = m & mask
        return jnp.where(m[:, None], sliced, 0), m

    # ---------------------------------------------- device-resident join
    def _build_join_partitions(self, table: RelationalTable, key: str,
                               payload: str):
        """Hash-partition the build side's {key, payload, ts} columns into
        device buckets and insert them into the module-global join build
        cache (one build per build-table version — the next probe hits).

        The PMU charges the partition-array upload **once** here:
        ``bytes_uploaded``/``uploads`` (it is a host→device transfer) plus
        the dedicated ``join_builds``/``bytes_join_build`` split the
        benchmarks report.  Warm probes charge nothing — the buckets are
        device-resident state, exactly like the row store itself.
        """
        from .planner import DEVICE_JOIN_PATH, _insert_build_index

        faults.maybe_fault("join_build", table=table.uid)
        words = table.words()
        parts = K.build_partitions(
            words[:, table.schema.word_offset(key)],
            words[:, table.schema.word_offset(payload)],
            words[:, table.ts_begin_word],
            words[:, table.ts_end_word],
        )
        self.stats.join_builds += 1
        self.stats.bytes_join_build += parts.nbytes
        self.stats.uploads += 1
        self.stats.bytes_uploaded += parts.nbytes
        _insert_build_index(parts, table, key, payload, DEVICE_JOIN_PATH)
        return parts

    def _op_partitions(self, op: JoinOp):
        """The op's build partitions: the compile-time cache hit, or a fresh
        build-and-insert (the sorted-index closure pattern of the host
        route — two identical joins compiled before either runs both miss
        and both insert; the same-key overwrite keeps occupancy exact)."""
        if op.partitions is not None:
            return op.partitions
        return self._build_join_partitions(op.right_table, op.key,
                                           op.right_proj)

    def _probe_join(self, words: jax.Array, partitions, key_word: int,
                    val_word: int, ts_word: int, ts: int, build_ts: bool,
                    route=None):
        """One probe pass with the per-query lowering-failure fallback: the
        Pallas grid pass when the revision supports it, else — or on any
        lowering error — the fused-gather XLA probe (same results).  Before
        dispatch the row tile is halved until the modeled working set (row
        tile + resident plane arrays) fits :meth:`_vmem_budget`; on a chip a
        build side too large for any tile takes the XLA probe, counted in
        ``kernel_fallbacks`` like every other fallback serve.  ``route``
        threads the caller's circuit-breaker key so repeated lowering
        failures flip the route ``open`` and skip the doomed attempt during
        the cooldown."""
        def xla_probe():
            return K.hash_join_xla(words, partitions, key_word, val_word,
                                   ts_word=ts_word, ts=ts, build_ts=build_ts)

        if self.revision == "xla":
            return xla_probe()
        vmem = self._vmem_budget(words)
        block_rows = self._probe_block_rows(partitions, words.shape[1], vmem,
                                            build_ts)
        if block_rows is None and self.interpret:
            # the interpreter has no VMEM to exhaust: the floor tile serves
            block_rows = MIN_FUSED_BLOCK_ROWS
        if block_rows is None or (route is not None
                                  and not self.breaker.allow(route)):
            self.stats.kernel_fallbacks += 1
            return xla_probe()
        self.stats.last_block_rows = block_rows
        pieces = _row_pieces(words, self._kernel_row_limit(
            words, (words.shape[1], 1, 1, 1), block_rows))
        try:
            faults.maybe_fault("lowering", op="join")
            outs = []
            for i, piece in enumerate(pieces):
                with trace.span("engine.hash_join", range=i,
                                rows=piece.shape[0],
                                lanes=partitions.kv_planes.shape[1]):
                    outs.append(K.hash_join(
                        piece, partitions, key_word, val_word,
                        ts_word=ts_word, ts=ts, build_ts=build_ts,
                        revision=self.revision, block_rows=block_rows,
                        interpret=self.interpret, vmem_limit=vmem))
        except Exception as err:
            if isinstance(err, faults.FaultError) and err.site != "lowering":
                raise
            # one query's lowering failure falls back to the XLA probe
            # instead of poisoning the batch
            if route is not None:
                self.breaker.record_failure(route)
            self.stats.kernel_fallbacks += 1
            return xla_probe()
        if route is not None:
            self.breaker.record_success(route)
        if len(outs) == 1:
            return outs[0]
        with trace.span("engine.combine", ranges=len(outs)):
            return tuple(jnp.concatenate(col) for col in zip(*outs))

    def _chip_limits(self, words: jax.Array) -> tuple[int, int | None]:
        """``(scoped VMEM a compiled kernel may take, HBM bytes)`` of the chip
        that holds ``words``, asked of the device once per engine."""
        if self._chip is None:
            device = next(iter(words.devices()))
            hbm = (device.memory_stats() or {}).get("bytes_limit")
            self._chip = (common.vmem_limit_bytes(device.device_kind), hbm)
        return self._chip

    def _vmem_budget(self, words: jax.Array) -> int:
        """The one VMEM budget both row-tile guards (fused pass, join probe)
        size against, and the scoped-VMEM limit the kernels compile with:
        the paper's SPM, ``vmem_bytes``, in interpret mode; compiled, the
        share of VMEM a kernel may take on the chip that holds ``words``."""
        if self.interpret:
            return self.vmem_bytes
        return self._chip_limits(words)[0]

    def _kernel_row_limit(self, words: jax.Array, widths,
                          block_rows: int) -> int | None:
        """Most rows one compiled kernel call over ``words`` takes
        (:func:`repro.kernels.common.kernel_row_limit` of the HBM its chip
        reports); ``None`` in interpret mode, where nothing is relaid out,
        or where the device reports no memory limit."""
        if self.interpret:
            return None
        hbm = self._chip_limits(words)[1]
        if hbm is None:
            return None
        return common.kernel_row_limit(hbm, widths, block_rows)

    def _probe_block_rows(self, partitions, row_words: int, vmem: int,
                          build_ts: bool = False) -> int | None:
        """The largest row tile (halving from ``block_rows``, never below
        ``MIN_FUSED_BLOCK_ROWS``) whose modeled probe working set fits
        ``vmem``; ``None`` when even the smallest tile does not."""
        block_rows = self.block_rows
        while K.probe_vmem_footprint_bytes(partitions, row_words, block_rows,
                                           build_ts) > vmem:
            if block_rows // 2 < MIN_FUSED_BLOCK_ROWS:
                return None
            block_rows //= 2
        return block_rows

    def _join_direct(self, op: JoinOp) -> JoinResult:
        """Solo join: stream the probe kernel over the device row-store
        chunks (no packed materialization).  Bus beats are charged per chunk
        via the union geometry of the probe-side request — the same request
        the op would contribute to a shared pass."""
        table = op.table
        parts = self._op_partitions(op)
        chunks = self.device_chunks(table)
        key_word = table.schema.word_offset(op.key)
        val_word = table.schema.word_offset(op.left_proj)
        snap = op.snapshot_ts is not None
        ts_word = table.ts_begin_word if snap else -1
        outs = [
            self._probe_join(chunk, parts, key_word, val_word, ts_word,
                             op.snapshot_ts or 0, snap,
                             route=(table.uid, "join"))
            for chunk in chunks
        ]
        acc_req = op.lower()  # its intervals are exactly the probe footprint
        self.stats.rows_projected += table.row_count
        for chunk in chunks:
            self.charge_scan(table, (acc_req,), row_count=chunk.shape[0])
        return JoinResult.concat([JoinResult(*o) for o in outs])

    def _finish_join(self, op: JoinOp, out) -> JoinResult:
        """Probe a shared-scan output: the op's probe-side scan rode the
        fused pass (packed block, or ``(packed, mask)`` under a snapshot —
        the mask being the probe rows' MVCC visibility); the bucket probe
        runs on that packed block, so the join costs the tick no extra
        row-store pass."""
        parts = self._op_partitions(op)
        packed, mask = out if isinstance(out, tuple) else (out, None)
        key_word, _ = op.view.column_words(op.key)
        val_word, _ = op.view.column_words(op.left_proj)
        s, r, m = self._probe_join(
            packed, parts, key_word, val_word, ts_word=-1,
            ts=op.snapshot_ts or 0, build_ts=op.snapshot_ts is not None,
            route=(op.table.uid, "join"),
        )
        if mask is not None:  # packed blocks carry no ts words: mask outside
            s = jnp.where(mask, s, 0)
            r = jnp.where(mask, r, 0)
            m = m & mask
        return JoinResult(s_proj=s, r_proj=r, matched=m)

    def scan_bytes(self, table: RelationalTable,
                   reqs: Sequence["KR.ScanRequest"],
                   row_count: int | None = None) -> int:
        """Bus-beat bytes of one pass serving ``reqs``: Eq. (3) bursts over
        the union of every request's enabled words.  ``row_count`` prices a
        pass over one chunk (default: the whole table).  The row stride is
        the schema's — unless a fused MVCC snapshot enables the hidden
        timestamp words, in which case the storage stride (what the stream
        walks) is the honest model.

        When the union touches encoded columns (paper §4), the pass is
        priced at the codecs' *narrow* word budget instead — each encoded
        word contributes ``codec.code_bytes`` per row rather than its full
        4-byte slot — capped by the plain Eq.(3) cost.  Pure: callers that
        estimate (serving-layer scan-sharing stats) and callers that charge
        (:meth:`charge_scan`) see the same number.
        """
        narrow, _ = self._scan_bytes_pair(table, reqs, row_count)
        return narrow

    def charge_scan(self, table: RelationalTable,
                    reqs: Sequence["KR.ScanRequest"],
                    row_count: int | None = None) -> int:
        """Book one pass's bus-beat bytes — the single charge point.

        ``bytes_from_dram`` takes the (possibly codec-narrowed) cost;
        ``bytes_saved_compression`` takes the plain-minus-narrow remainder,
        so ``bytes_from_dram + bytes_saved_compression`` is always the
        uncompressed Eq.(3) cost of the same passes."""
        narrow, plain = self._scan_bytes_pair(table, reqs, row_count)
        self.stats.bytes_from_dram += narrow
        self.stats.bytes_saved_compression += plain - narrow
        return narrow

    def _scan_bytes_pair(self, table: RelationalTable,
                         reqs: Sequence["KR.ScanRequest"],
                         row_count: int | None = None) -> tuple[int, int]:
        """(narrow, plain) Eq.(3) bytes of one pass; equal when no enabled
        word is codec-backed."""
        max_end = max(o + w for r in reqs for o, w in K.request_intervals(r))
        row_bytes = table.schema.row_bytes
        if max_end > row_bytes:
            row_bytes = table.row_words * WORD
        rows = table.row_count if row_count is None else row_count
        union = K.union_geometry(reqs, row_bytes=row_bytes, row_count=rows)
        plain = bytes_moved(union)["rme"]
        codecs = getattr(table, "codecs", None)
        if not codecs:
            return plain, plain
        enabled: set[int] = set()
        for r in reqs:
            for o, w in K.request_intervals(r):
                enabled.update(range(o // WORD, -(-(o + w) // WORD)))
        by_word = {table.schema.word_offset(n): c for n, c in codecs.items()}
        if not any(w in enabled for w in by_word):
            return plain, plain
        per_row = sum(
            by_word[w].code_bytes if w in by_word else WORD for w in enabled
        )
        return min(plain, rows * per_row), plain

    # FIFO cap on cached decoded client reads — decoded string columns can be
    # large, and one live (table-version, result) pair per view is the norm
    DECODE_CACHE_MAX = 64

    def decode_column(self, table: RelationalTable, name: str, codes,
                      token: tuple = ()):
        """Decode-on-finalize: map a packed result's raw code words for
        column ``name`` back to values, cached per table version.

        This is the *only* place the engine decodes — the fused pass
        operates on raw codes end to end.  ``token`` distinguishes reads of
        the same column under different result shapes (e.g. a snapshot
        view's visible-row slice).  The cache key folds in ``version`` and
        ``storage_epoch`` so any append/update/refit invalidates naturally.
        """
        codec = table.codecs[name]
        key = (table.uid, name, table.version,
               getattr(table, "storage_epoch", 0), token)
        if key in self._decode_cache:
            self.stats.decode_cache_hits += 1
            return self._decode_cache[key]
        self.stats.decodes += 1
        out = codec.decode(codes)
        while len(self._decode_cache) >= self.DECODE_CACHE_MAX:
            self._decode_cache.pop(next(iter(self._decode_cache)))
        self._decode_cache[key] = out
        return out

    def _fused_block_rows(self, reqs: Sequence["KR.ScanRequest"],
                          row_words: int, vmem: int) -> int:
        """SPM budget guard: halve the row tile until the fused pass's modeled
        VMEM working set fits ``vmem`` (:meth:`_vmem_budget`; never below
        the floor)."""
        block_rows = self.block_rows
        while (block_rows // 2 >= MIN_FUSED_BLOCK_ROWS
               and K.scan_vmem_footprint_bytes(reqs, row_words, block_rows)
               > vmem):
            block_rows //= 2
        self.stats.last_block_rows = block_rows
        return block_rows

    def aggregate_async(
        self,
        table: RelationalTable,
        agg_col: str,
        pred_col: str | None = None,
        pred_op: str = "none",
        pred_k=0,
        snapshot_ts: int | None = None,
    ) -> jax.Array:
        """Non-blocking fused aggregate: returns the device ``[sum, count]`` pair.

        Nothing syncs with the host here — the caller decides when (whether)
        to pull the scalars down, so batched query loops can enqueue many
        aggregates before blocking once.  The row store is read from the
        device-resident buffer: repeated aggregates over an unchanged table
        perform zero host→device transfers after the first call, and a
        mutated table ships only its write delta.  ``snapshot_ts`` fuses the
        MVCC visibility test in-scan: rows outside the snapshot contribute
        nothing, so concurrent writers never perturb a pinned reader.  No
        ``bytes_to_cpu`` are charged here — nothing crosses to the host until
        a caller syncs (the blocking :meth:`aggregate` charges its 8 bytes).
        This is sugar for a one-op :meth:`execute_many` batch, so it shares
        the same accounting (including the bus-beat charge for the enabled
        aggregate/predicate words).
        """
        op = AggregateOp(table, agg_col, pred_col=pred_col, pred_op=pred_op,
                         pred_k=pred_k, snapshot_ts=snapshot_ts)
        return self.execute_many([op])[0]

    def aggregate(
        self,
        table: RelationalTable,
        agg_col: str,
        pred_col: str | None = None,
        pred_op: str = "none",
        pred_k=0,
        snapshot_ts: int | None = None,
    ) -> tuple[float, float]:
        """Fused near-memory ``SELECT SUM(agg), COUNT(*) WHERE pred`` (Q0/Q3).

        Only a 2-float scalar leaves the engine; the MVCC snapshot test is
        fused when a snapshot time is given.  This is the blocking wrapper
        around :meth:`aggregate_async` — the ``float()`` calls are the only
        host sync.
        """
        out = self.aggregate_async(
            table, agg_col, pred_col=pred_col, pred_op=pred_op, pred_k=pred_k,
            snapshot_ts=snapshot_ts,
        )
        self.stats.bytes_to_cpu += 8  # the [sum, count] pair crosses on sync
        return float(out[0]), float(out[1])

    def vmem_budget_bytes(self, geom: TableGeometry) -> int:
        """The 'area report' analogue: VMEM working set of one engine step."""
        return vmem_footprint_bytes(geom, self.block_rows, self.revision)
