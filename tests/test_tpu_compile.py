"""Compile the main-path kernels for a described TPU v5e, at real geometry.

Nothing runs: each test lowers a kernel for a v5e chip that is described,
not attached (``jax.experimental.topologies``), and asserts that Mosaic
accepted it (``tpu_custom_call`` in the compiled HLO).  This is what
interpret-mode tests cannot show — scalar stores to VMEM, block shapes off
the (8, 128) tiling, more VMEM than a kernel may use, programs larger than
HBM.  Sizes are the bring-up smoke's (``chip_smoke.py``): 2^23 rows of 18
storage words for single requests; the mixed tick and the join probe at the
rows of one kernel call the engine makes on a v5e
(``common.kernel_row_limit``), whose temporaries must then fit the share of
HBM that limit budgets for them.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU compiler library, and every test worker
must collect the same tests.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import RelationalMemoryEngine
from repro.core.engine import _row_widths
from repro.core.schema import TableGeometry, benchmark_schema
from repro.kernels import common
from repro.kernels import rme_filter as KF
from repro.kernels import rme_join as KJ
from repro.kernels import rme_project as KP
from repro.kernels import rme_scan_multi as KR

ROWS = 1 << 23  # chip_smoke.py's relation
ROW_WORDS = 18  # 16 int32 columns + the two hidden MVCC timestamp words
TS_WORD = 16
BLOCK_ROWS = common.DEFAULT_BLOCK_ROWS
V5E_HBM_BYTES = 16_909_336_064  # the bytes_limit a v5e reports
# the smoke's build side: 65,536 unique keys -> P = 4096 buckets, C = 19
BUCKETS, CAPACITY = 4096, 19

SCHEMA = benchmark_schema(64, 4)
GEOM = TableGeometry.from_schema(SCHEMA, ["A1", "A2"], row_count=0)
PROJECT = KR.ProjectRequest(GEOM)
FILTER = KR.FilterRequest(GEOM, pred_word=2, pred_op="gt")
AGGREGATE = KR.AggregateRequest(agg_word=0, pred_word=3, pred_op="lt")
GROUPBY = KR.GroupByRequest(group_word=4, agg_word=0, num_groups=16)


def _pinned(req):
    """The request as a snapshot read compiles it (fused MVCC test); a pinned
    projection is a filter with the always-true ``none`` predicate."""
    if isinstance(req, KR.ProjectRequest):
        return KR.FilterRequest(req.geom, pred_word=0, pred_op="none",
                                ts_word=TS_WORD)
    return dataclasses.replace(req, ts_word=TS_WORD)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shape(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _call_rows(widths):
    """Rows of one kernel call the engine makes on a v5e."""
    return common.kernel_row_limit(V5E_HBM_BYTES, widths, BLOCK_ROWS)


def _assert_fits_call_budget(compiled):
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= common.KERNEL_HBM_FRACTION * V5E_HBM_BYTES


def _lower_scan(sharding, requests, rows):
    """The fused pass as the engine lowers it on a v5e: the default row
    tile, the chip's scoped-VMEM limit."""
    reqs = tuple(KR._strip_dynamic(r) for r in requests)
    n = len(reqs)
    kind = next(iter(sharding.device_set)).device_kind
    return KR._scan_multi.lower(
        _shape(sharding, rows, ROW_WORDS), _shape(sharding, n, 1),
        _shape(sharding, n, 1), requests=reqs, block_rows=BLOCK_ROWS,
        interpret=False, vmem_limit=common.vmem_limit_bytes(kind))


def _compile_scan(sharding, requests, rows):
    return _assert_kernel(_lower_scan(sharding, requests, rows))


@pytest.mark.parametrize("request_", [
    PROJECT, FILTER, AGGREGATE, GROUPBY, _pinned(PROJECT), _pinned(FILTER),
    _pinned(AGGREGATE), _pinned(GROUPBY),
], ids=["project", "filter", "aggregate", "groupby", "project_ts",
        "filter_ts", "aggregate_ts", "groupby_ts"])
def test_scan_multi_single_request_compiles(one_chip, request_):
    _compile_scan(one_chip, (request_,), ROWS)


@pytest.mark.parametrize("pinned", [False, True], ids=["tick1", "tick2"])
def test_scan_multi_mixed_tick_compiles(one_chip, pinned):
    """The smoke's mixed tick: projection (shared with the join's packed
    probe input), filter, aggregate and group-by in one pass."""
    reqs = (PROJECT, FILTER, AGGREGATE, GROUPBY)
    if pinned:
        reqs = tuple(_pinned(r) for r in reqs)
    widths = _row_widths(_shape(one_chip, ROWS, ROW_WORDS), reqs)
    _assert_fits_call_budget(
        _compile_scan(one_chip, reqs, _call_rows(widths)))


def test_mixed_tick_in_one_call_exceeds_hbm(one_chip):
    """Why the engine cuts row ranges: the smoke's mixed pass over all 2^23
    rows in one call needs more HBM than a v5e has, for the row-major
    copies of its operand and outputs alone."""
    with pytest.raises(Exception, match="Ran out of memory in memory space hbm"):
        _lower_scan(one_chip, (PROJECT, FILTER, AGGREGATE, GROUPBY),
                    ROWS).compile()


def _partitions(sharding, buckets, capacity):
    """Shapes of a built ``JoinPartitions`` at ``(buckets, capacity)``."""
    bucket = _shape(sharding, buckets, capacity)
    planes = jax.ShapeDtypeStruct((buckets, KJ.plane_lanes(capacity)),
                                  jnp.int8, sharding=sharding)
    return KJ.JoinPartitions(bucket, bucket, bucket, bucket, planes, planes)


@pytest.mark.parametrize("build_ts", [False, True], ids=["unpinned", "pinned"])
@pytest.mark.parametrize("probe_side", ["packed", "row_store"])
def test_hash_join_probe_compiles(topo, one_chip, probe_side, build_ts):
    """The byte-plane probe at the smoke's (P, C), with the row tile and
    scoped-VMEM limit the engine's guard picks — so the footprint model is
    checked against the compiler, too."""
    limit = common.vmem_limit_bytes(topo.devices[0].device_kind)
    parts = _partitions(one_chip, BUCKETS, CAPACITY)
    assert parts.kv_planes.shape == (BUCKETS, 256)  # 8 planes x 19 slots
    width, key_word, ts_word = ((2, 1, -1) if probe_side == "packed"
                                else (ROW_WORDS, 1, TS_WORD))
    block_rows = RelationalMemoryEngine()._probe_block_rows(
        parts, width, limit, build_ts)
    assert block_rows == BLOCK_ROWS
    rows = _call_rows((width, 1, 1, 1))
    _assert_fits_call_budget(_assert_kernel(KJ._hash_join.lower(
        _shape(one_chip, rows, width), parts.kv_planes,
        parts.ts_planes if build_ts else None, _shape(one_chip, 1, 1),
        key_word=key_word, val_word=0, ts_word=ts_word, build_ts=build_ts,
        capacity=CAPACITY, block_rows=block_rows, interpret=False,
        vmem_limit=KJ.probe_vmem_footprint_bytes(parts, width, block_rows,
                                                 build_ts))))


def test_project_mlp_compiles(one_chip):
    _assert_kernel(KP.project.lower(
        _shape(one_chip, ROWS, ROW_WORDS), geom=GEOM, revision="mlp",
        block_rows=BLOCK_ROWS, interpret=False))


def test_filter_project_pinned_compiles(one_chip):
    """A solo snapshot projection: the filter kernel with the ``none``
    predicate, whose mask comes from the row ids and timestamps alone."""
    _assert_kernel(KF.filter_project.lower(
        _shape(one_chip, ROWS, ROW_WORDS), geom=GEOM, pred_word=0,
        pred_op="none", ts_word=TS_WORD, block_rows=BLOCK_ROWS,
        interpret=False))
