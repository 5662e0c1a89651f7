"""Device-resident hash equi-join — the last §8 offload escape hatch closed.

The paper's closing claim is that Relational Memory "can be easily extended
to support offloading of a number of operations to hardware, e.g., selection,
group by, aggregation, and joins".  Selection, aggregation, and group-by ride
the heterogeneous one-pass scan (``rme_scan_multi``); joins, until now, were
slimmed to {key, payload} on device and then sort-probed on the CPU.  This
module moves the probe itself next to the data:

* :func:`build_partitions` hash-partitions the build side's
  ``{key, payload, __ts_begin, __ts_end}`` columns into **static device
  buckets** — a ``(P, C)`` array per column, ``P`` buckets of capacity ``C``
  (the observed maximum occupancy, so nothing ever overflows).  Built once
  per build-table version and cached exactly like the q5 sorted index
  (:mod:`repro.core.planner`).
* :func:`hash_join` probes in one Pallas grid pass that streams the probe
  rows — straight out of the :class:`~repro.core.engine.DeviceRowStore`
  chunks, or out of a packed block the shared scan already produced — and
  emits the same static-shape contract as the host route: one slot per probe
  row (``s_proj``, ``r_proj``) plus a ``matched`` validity mask.

TPU adaptation: buckets are selected with a one-hot MXU contraction (the
``groupby_sum`` idiom), not a gather.  Every int32 bucket word also travels
as four **int8 byte planes** — byte ``j`` of each word, its raw bits read as
two's complement — laid side by side in one ``(P, K)`` int8 array per pair
of columns (``kv_planes``: key and payload; ``ts_planes``: begin and end).
The one-hot is exactly 0 or 1, so a single int8 × int8 → int32 pass selects
every byte exactly (one nonzero term per output) at the MXU's integer rate,
twice its bfloat16 rate; ``& 0xFF`` on the VPU recovers ``0..255``.  A
second, eight times smaller bfloat16 pass (``0..255`` is exact in bfloat16's
8 significant bits) pairs the bytes into exact 16-bit halves on whole
128-lane tiles, and shifts and ORs rebuild the original bit pattern —
bit-exact selection, no dynamic indexing in the kernel.  ``K`` is ``8·C``
padded to whole 128-lane tiles: it follows the observed capacity.

The bucket hash is **Fibonacci multiplicative hashing**: ``bucket = (key *
2654435761) >>> (32 - log2 P)`` (the top bits of the wrapped product, same
modular arithmetic in numpy, Pallas, and XLA).  Taking high bits matters: a
plain ``key mod P`` degenerates to one bucket for stride-aligned keys (every
multiple of P lands in bucket 0), blowing the dense ``(P, C)`` arrays up to
``P × n`` words, while the multiplicative mix spreads any stride pattern
uniformly — capacity only degenerates if the build side violates its
documented primary-key (duplicate-free) contract.  Empty bucket slots are
filled with ``1`` in bucket 0 and ``0`` elsewhere: ``hash(0) = 0`` and
``hash(1) = 2654435761 >>> (32 - log2 P) >= 1``, so a fill value can never
hash to its own bucket, and since a probe key only ever compares against its
own bucket's slots, fills can never false-match.

MVCC fuses on both sides: the probe pass tests the probe rows' hidden
timestamp words in-scan (``ts_word >= 0``), and the bucket ``begin``/``end``
columns let the same snapshot test run against the *build* rows — one cached
partition set serves any snapshot time, because ``ts`` is a traced operand.

``hash_join_xla`` is the fused-gather fallback (plain ``jnp.take`` bucket
lookup) used for the ``xla`` revision, for a build side whose plane arrays
do not fit the chip's VMEM (decided before dispatch from
:func:`probe_vmem_footprint_bytes`), and as the per-query escape when the
Pallas probe fails to lower, mirroring ``scan_multi_xla``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import DEFAULT_BLOCK_ROWS, pad_rows, resolve_interpret, tile_row_ids

# target average bucket occupancy: P is the smallest power of two with
# n_rows / P <= TARGET_BUCKET_LOAD (capacity C is then the observed maximum)
TARGET_BUCKET_LOAD = 16

# Fibonacci hashing constant (2654435761 = floor(2^32 / golden ratio)); the
# int32 spelling is its two's-complement bit pattern — jnp int32 multiplies
# wrap, giving the same modular product as the numpy uint32 build-side math
MIX_UINT32 = np.uint32(2654435761)
MIX_INT32 = np.int32(np.uint32(2654435761).astype(np.int64) - (1 << 32))


class JoinPartitions(NamedTuple):
    """The build side as static device buckets: four ``(P, C)`` int32 arrays
    and their byte planes, two ``(P, K)`` int8 arrays (1 B a lane).

    A NamedTuple of arrays on purpose — the planner's join build cache
    accounts entry bytes by iterating the entry, exactly as it does for the
    sorted-index tuples it already holds.  Empty ``keys`` slots hold a fill
    that provably hashes to a *different* bucket (see :func:`bucket_fills`),
    so they can never false-match; their ``begin=1, end=0`` timestamps are
    never visible at any snapshot either.  The int32 arrays feed the XLA
    probe (:func:`hash_join_xla`), the planes the Pallas probe.
    """

    keys: jax.Array  # (P, C) raw int32 key words
    vals: jax.Array  # (P, C) raw int32 payload words
    begin: jax.Array  # (P, C) __ts_begin of each build row
    end: jax.Array  # (P, C) __ts_end of each build row
    kv_planes: jax.Array  # (P, K) int8 bytes 0..3 of keys, then of vals
    ts_planes: jax.Array  # (P, K) int8 bytes 0..3 of begin, then of end

    @property
    def num_buckets(self) -> int:
        return self.keys.shape[0]

    @property
    def capacity(self) -> int:
        return self.keys.shape[1]

    @property
    def nbytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self)


def num_buckets_for(n_rows: int) -> int:
    """Smallest power-of-two bucket count with average load <= the target
    (never below 2, so the hash has at least one output bit)."""
    p = 2
    while p * TARGET_BUCKET_LOAD < n_rows:
        p <<= 1
    return p


def bucket_of_np(key: np.ndarray, p: int) -> np.ndarray:
    """Fibonacci bucket hash, numpy spelling: top ``log2 p`` bits of the
    wrapped ``key * 2654435761`` product.  Must stay bit-identical to the
    in-kernel spelling (:func:`_bucket_of`)."""
    mixed = np.asarray(key, dtype=np.int32).view(np.uint32) * MIX_UINT32
    return (mixed >> np.uint32(32 - (p.bit_length() - 1))).astype(np.int64)


def _bucket_of(key, p: int):
    """Fibonacci bucket hash, traced (jnp) spelling — int32 wrap-around
    multiply + logical shift, bit-identical to :func:`bucket_of_np`."""
    mixed = key * jnp.int32(MIX_INT32)
    return jax.lax.shift_right_logical(mixed, 32 - (p.bit_length() - 1))


def bucket_fills(p: int) -> np.ndarray:
    """Per-bucket empty-slot key fills that provably never false-match:
    ``hash(0) = 0`` (safe everywhere but bucket 0) and ``hash(1) =
    2654435761 >>> (32 - log2 p) >= 1`` for any ``p >= 2`` (safe in bucket
    0).  A probe key equal to a fill hashes to the fill's own bucket, which
    is never the bucket holding it."""
    fills = np.zeros(p, dtype=np.int32)
    fills[0] = 1
    return fills


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def plane_lanes(capacity: int) -> int:
    """Lanes ``K`` of a byte-plane array: eight planes of ``C`` slots side
    by side, padded to whole 128-lane tiles."""
    return _pad(8 * capacity, 128)


def estimated_partition_bytes(n_rows: int) -> int:
    """Planner-side estimate of a build table's partition-array bytes (four
    ``(P, C)`` int32 arrays and two ``(P, K)`` int8 plane arrays at the
    target load) — the build-upload term of the join route cost model,
    available before anything is built."""
    p = num_buckets_for(n_rows)
    c = max(1, -(-n_rows // p))
    return 4 * p * c * 4 + 2 * p * plane_lanes(c)


def _byte_planes(a: np.ndarray, b: np.ndarray) -> jax.Array:
    """Two ``(P, C)`` int32 columns as one ``(P, K)`` int8 array: lanes
    ``[q·C, (q+1)·C)`` hold plane ``q = 4w + j``, byte ``j`` of column
    ``w``, its raw bits read as two's complement."""
    p, c = a.shape
    u = np.stack([a, b]).view(np.uint32)[:, None]  # (2, 1, P, C)
    shifts = np.arange(0, 32, 8, dtype=np.uint32)[None, :, None, None]
    planes = ((u >> shifts) & np.uint32(0xFF)).astype(np.uint8)  # (2, 4, P, C)
    out = np.zeros((p, plane_lanes(c)), np.uint8)
    out[:, : 8 * c] = planes.transpose(2, 0, 1, 3).reshape(p, 8 * c)
    return jnp.asarray(out.view(np.int8))


@functools.lru_cache(maxsize=None)
def _pair_weights(capacity: int) -> np.ndarray:
    """``(K, 4·Cp)`` bfloat16 matrix, ``Cp = C`` padded to 128 lanes, that
    pairs selected byte planes into 16-bit halves: slot ``s`` of plane ``q``
    goes to lane ``s`` of half ``q // 2``, times 256 for odd ``q``.  Half
    ``2w`` holds the low 16 bits of column ``w``, half ``2w + 1`` the high,
    each on whole 128-lane tiles."""
    c, cp = capacity, _pad(capacity, 128)
    q, s = np.divmod(np.arange(8 * c), c)
    w = np.zeros((plane_lanes(c), 4 * cp), np.float32)
    w[np.arange(8 * c), cp * (q // 2) + s] = np.where(q % 2, 256.0, 1.0)
    w = w.astype(jnp.bfloat16)
    w.flags.writeable = False
    return w


def build_partitions(
    key: np.ndarray,
    val: np.ndarray,
    ts_begin: np.ndarray | None = None,
    ts_end: np.ndarray | None = None,
) -> JoinPartitions:
    """Hash-partition the build side's raw column words into device buckets.

    Host-side preprocessing (numpy), run once per build-table version; the
    returned arrays are the device-resident state every subsequent probe
    reuses.  The Fibonacci hash spreads any stride-aligned key pattern
    uniformly, so capacity stays near the target load for every
    duplicate-free key set; genuinely repeated keys (a violation of the
    build side's primary-key contract, or MVCC version pairs from updates)
    degrade capacity, never correctness.
    """
    key = np.asarray(key, dtype=np.int32)
    val = np.asarray(val, dtype=np.int32)
    n = key.shape[0]
    p = num_buckets_for(n)
    g = bucket_of_np(key, p)
    counts = np.bincount(g, minlength=p)
    cap = max(int(counts.max()) if n else 1, 1)
    # slot index of each row within its bucket (stable order within buckets)
    order = np.argsort(g, kind="stable")
    starts = np.cumsum(counts) - counts
    slot = np.arange(n, dtype=np.int64) - np.repeat(starts, counts)
    gb, sb = g[order], slot

    def scatter(fill: np.ndarray, values: np.ndarray) -> np.ndarray:
        arr = np.broadcast_to(fill[:, None], (p, cap)).copy()
        arr[gb, sb] = values[order]
        return arr

    keys = scatter(bucket_fills(p), key)  # fills provably never match
    vals = scatter(np.zeros(p, np.int32), val)
    begin = scatter(np.ones(p, np.int32),
                    np.zeros(n, np.int32) if ts_begin is None
                    else np.asarray(ts_begin, dtype=np.int32))
    end = scatter(np.zeros(p, np.int32),
                  np.zeros(n, np.int32) if ts_end is None
                  else np.asarray(ts_end, dtype=np.int32))
    return JoinPartitions(
        keys=jnp.asarray(keys), vals=jnp.asarray(vals),
        begin=jnp.asarray(begin), end=jnp.asarray(end),
        kv_planes=_byte_planes(keys, vals),
        ts_planes=_byte_planes(begin, end),
    )


# ------------------------------------------------------------ Pallas probe
def _select_words(onehot: jax.Array, planes: jax.Array,
                  weights: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Bit-exact per-row bucket selection on the MXU: an int8 pass with
    int32 results, ``(B, P) @ (P, K)``, selects every byte (one nonzero
    term per output, the stored byte as two's complement; ``& 0xFF`` gives
    ``0..255``), then a bfloat16 pass with float32 results, ``(B, K) @ (K,
    4·Cp)``, pairs the bytes into 16-bit halves (``b_lo + 256·b_hi <
    2^16``, one or two nonzero terms).  Shifts and ORs rebuild both
    columns' int32 words, ``(B, Cp)`` each; slots at and above ``C`` read 0.

    The halves land on whole 128-lane tiles on purpose: slicing bytes at
    unaligned lane offsets and shifting them into place compiled to wrong
    bits on a v5e, while the pairing pass is exact there."""
    sel = jnp.dot(onehot, planes, preferred_element_type=jnp.int32) & 0xFF
    halves = jnp.dot(sel.astype(jnp.bfloat16), weights,
                     preferred_element_type=jnp.float32)
    cp = halves.shape[1] // 4
    h = [halves[:, cp * t : cp * (t + 1)].astype(jnp.int32) for t in range(4)]
    return (h[1] << 16) | h[0], (h[3] << 16) | h[2]


def _probe_kernel(key_word, val_word, ts_word, build_ts, capacity, n_rows,
                  x_ref, w_ref, kv_ref, *refs):
    ts_planes_ref = refs[0] if build_ts else None
    ts_ref, s_ref, r_ref, m_ref = refs[-4:]
    i = pl.program_id(0)
    block_rows = x_ref.shape[0]
    p = kv_ref.shape[0]
    s_key = x_ref[:, key_word]
    g = _bucket_of(s_key, p)
    onehot = (
        g[:, None] == jax.lax.iota(jnp.int32, p)[None, :]
    ).astype(jnp.int8)  # (B, P), exact
    weights = w_ref[...]
    keys, vals = _select_words(onehot, kv_ref[...], weights)  # (B, Cp) each
    # padding slots read key 0, a word a probe row may hold
    slot = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    match = (keys == s_key[:, None]) & (slot < capacity)
    ts = ts_ref[0, 0]
    if build_ts:
        begin, end = _select_words(onehot, ts_planes_ref[...], weights)
        match = match & (begin <= ts) & (ts < end)
    ridx = tile_row_ids(i, block_rows)
    valid = ridx < n_rows
    if ts_word >= 0:
        valid = valid & (x_ref[:, ts_word] <= ts) & (ts < x_ref[:, ts_word + 1])
    matched = jnp.any(match, axis=1) & valid
    # primary-key build side: at most one slot matches
    r_val = jnp.sum(jnp.where(match, vals, 0), axis=1)
    s_ref[...] = jnp.where(valid, x_ref[:, val_word], 0)[:, None]
    r_ref[...] = jnp.where(matched, r_val, 0)[:, None]
    m_ref[...] = matched[:, None].astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("key_word", "val_word", "ts_word", "build_ts",
                     "capacity", "block_rows", "interpret", "vmem_limit"),
)
def _hash_join(
    words: jax.Array,
    kv_planes: jax.Array,
    ts_planes: jax.Array | None,  # read only when ``build_ts``
    ts_arr: jax.Array,  # (1, 1) int32 traced snapshot time
    key_word: int,
    val_word: int,
    ts_word: int,
    build_ts: bool,
    capacity: int,
    block_rows: int,
    interpret: bool | None,
    vmem_limit: int | None,
):
    n, row_words = words.shape
    x = pad_rows(words, block_rows)
    n_pad = x.shape[0]
    full = pl.BlockSpec(kv_planes.shape, lambda i: (0, 0))
    planes = (kv_planes, ts_planes) if build_ts else (kv_planes,)
    weights = jnp.asarray(_pair_weights(capacity))
    col = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((n_pad, 1), jnp.int32)
    return pl.pallas_call(
        functools.partial(_probe_kernel, key_word, val_word, ts_word,
                          build_ts, capacity, n),
        grid=(n_pad // block_rows,),
        name="rme_hash_join",
        in_specs=[
            pl.BlockSpec((block_rows, row_words), lambda i: (i, 0)),
            pl.BlockSpec(weights.shape, lambda i: (0, 0)),
            *(full for _ in planes),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[col, col, col],
        out_shape=[out_shape, out_shape, out_shape],
        compiler_params=(None if vmem_limit is None else
                         pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)),
        interpret=resolve_interpret(interpret),
    )(x, weights, *planes, ts_arr)


def hash_join(
    words: jax.Array,
    partitions: JoinPartitions,
    key_word: int,
    val_word: int,
    ts_word: int = -1,
    ts: int = 0,
    build_ts: bool = False,
    revision: str = "mlp",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
    vmem_limit: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Probe ``words`` (a row-store chunk or a packed block) against cached
    build partitions; returns ``(s_proj, r_proj, matched)`` with one slot per
    probe row.

    ``key_word``/``val_word`` address the probe key and payload within the
    row stride — schema offsets when streaming the device row store, packed
    offsets when probing a shared-scan output.  ``ts_word >= 0`` fuses the
    probe-side MVCC test from the hidden timestamp words; ``build_ts`` fuses
    the same test against the build rows' bucketed timestamps.  ``ts`` is a
    traced operand: distinct snapshot times never retrace.  Rows are
    position-local, so per-chunk outputs concatenate (the
    ``scan_multi_chunked`` contract).  ``vmem_limit`` raises the compiled
    kernel's scoped-VMEM limit (the plane arrays stay resident); size it
    with :func:`probe_vmem_footprint_bytes` before dispatch.
    """
    if revision == "xla":
        return hash_join_xla(words, partitions, key_word, val_word,
                             ts_word=ts_word, ts=ts, build_ts=build_ts)
    ts_arr = jnp.asarray([[ts]], dtype=jnp.int32)
    n = words.shape[0]
    s, r, m = _hash_join(
        words, partitions.kv_planes, partitions.ts_planes if build_ts else None,
        ts_arr, key_word=key_word, val_word=val_word, ts_word=ts_word,
        build_ts=build_ts, capacity=partitions.capacity,
        block_rows=block_rows, interpret=interpret, vmem_limit=vmem_limit,
    )
    return s[:n, 0], r[:n, 0], m[:n, 0].astype(bool)


@functools.partial(
    jax.jit,
    static_argnames=("key_word", "val_word", "ts_word", "build_ts"),
)
def _hash_join_xla(words, bk, bv, bb, be, ts_arr, key_word, val_word,
                   ts_word, build_ts):
    p = bk.shape[0]
    s_key = words[:, key_word]
    g = _bucket_of(s_key, p)
    match = jnp.take(bk, g, axis=0) == s_key[:, None]  # (N, C)
    ts = ts_arr[0, 0]
    if build_ts:
        match = match & (jnp.take(bb, g, axis=0) <= ts)
        match = match & (ts < jnp.take(be, g, axis=0))
    valid = jnp.ones(s_key.shape, dtype=bool)
    if ts_word >= 0:
        valid = (words[:, ts_word] <= ts) & (ts < words[:, ts_word + 1])
    matched = jnp.any(match, axis=1) & valid
    r_val = jnp.sum(jnp.where(match, jnp.take(bv, g, axis=0), 0), axis=1)
    return (
        jnp.where(valid, words[:, val_word], 0),
        jnp.where(matched, r_val, 0),
        matched,
    )


def hash_join_xla(
    words: jax.Array,
    partitions: JoinPartitions,
    key_word: int,
    val_word: int,
    ts_word: int = -1,
    ts: int = 0,
    build_ts: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused-gather probe fallback: one ``jnp.take`` bucket lookup per
    partition column, then the same match/visibility math as the Pallas pass.
    Lowers anywhere; the ``xla`` revision and per-query lowering-failure
    fallback both dispatch here."""
    ts_arr = jnp.asarray([[ts]], dtype=jnp.int32)
    return _hash_join_xla(words, partitions.keys, partitions.vals,
                          partitions.begin, partitions.end, ts_arr,
                          key_word=key_word,
                          val_word=val_word, ts_word=ts_word,
                          build_ts=build_ts)


def probe_vmem_footprint_bytes(
    partitions: JoinPartitions, row_words: int,
    block_rows: int = DEFAULT_BLOCK_ROWS, build_ts: bool = False,
) -> int:
    """Modeled VMEM working set of one compiled probe grid step, in bytes.

    Every VMEM array is tiled ``(8, 128)``, so narrow minor dimensions are
    charged at 128 lanes: the double-buffered row tile and three ``(B, 1)``
    output columns, the double-buffered ``(P, K)`` int8 plane arrays (two
    with ``build_ts``) and bfloat16 ``(K, 4·Cp)`` pairing weights, the int32
    compare and int8 one-hot ``(B, P)``, and per plane array the int32
    ``(B, K)`` selected bytes, their bfloat16 copy and the float32 ``(B,
    4·Cp)`` halves.  An upper bound of what Mosaic allocates, checked
    against compiles for a v5e in ``tests/test_tpu_compile.py``.
    """
    n_planes = 2 if build_ts else 1
    b = _pad(block_rows, 8)
    p = _pad(partitions.num_buckets, 8)
    k = partitions.kv_planes.shape[1]
    halves = 4 * _pad(partitions.capacity, 128)
    tiles = 2 * b * (_pad(row_words, 128) + 3 * 128) * 4
    planes = 2 * (n_planes * p * k * 1 + k * halves * 2)
    onehot = b * p * (4 + 1)
    selected = n_planes * b * (k * (4 + 2) + halves * 4)
    return tiles + planes + onehot + selected


def broadcast_partitions(
    partitions: JoinPartitions, devices,
) -> list[JoinPartitions]:
    """Shard-local entry point: replicate the (small) build-side partition
    set onto every shard's device — the join's only collective.

    ``devices`` is one entry per shard; ``None`` means a logical shard on the
    current device (the replica is the original, no transfer).  The sharded
    engine charges ``(shards - 1) * partitions.nbytes`` of interconnect
    traffic for this broadcast — build partitions are O(build rows), never
    O(probe rows), which is what keeps collective bytes proportional to the
    smaller relation."""
    return [
        partitions if d is None else jax.device_put(partitions, d)
        for d in devices
    ]
