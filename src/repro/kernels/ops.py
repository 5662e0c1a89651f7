"""Public jit'd entry points for the RME kernel suite.

One import surface for the engine and the benchmarks; every function has a
bit-exact (or float-tolerant) oracle in ``ref.py`` and an interpret-mode sweep
in ``tests/test_kernels_*.py``.  ``revision`` selects the paper's hardware
revision; ``"xla"`` is the pure-XLA production path used when the program is
lowered for targets where the Pallas TPU kernels don't apply (CPU, dry-run).
"""

from __future__ import annotations

import jax

from repro.core.schema import TableGeometry

from .rme_aggregate import aggregate, groupby_sum
from .rme_filter import filter_project
from .rme_join import (
    JoinPartitions,
    broadcast_partitions,
    build_partitions,
    hash_join,
    hash_join_xla,
    probe_vmem_footprint_bytes,
)
from .rme_project import (
    DEFAULT_BLOCK_ROWS,
    project,
    project_xla,
    vmem_footprint_bytes,
)
from .rme_project_multi import project_multi, project_multi_xla
from .rme_scan_multi import (
    AggregateRequest,
    FilterRequest,
    GroupByRequest,
    ProjectRequest,
    combine_chunk_outputs,
    reduced_result_bytes,
    request_intervals,
    scan_multi,
    scan_multi_chunked,
    scan_multi_xla,
    scan_shard,
    scan_vmem_footprint_bytes,
    union_geometry,
)

REVISIONS = ("bsl", "pck", "mlp", "xla")


def project_any(
    words: jax.Array,
    geom: TableGeometry,
    revision: str = "mlp",
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Dispatch projection across revisions, including the XLA path."""
    if revision == "xla":
        return project_xla(words, geom)
    return project(words, geom, revision=revision, block_rows=block_rows,
                   interpret=interpret)


__all__ = [
    "REVISIONS",
    "DEFAULT_BLOCK_ROWS",
    "AggregateRequest",
    "FilterRequest",
    "GroupByRequest",
    "JoinPartitions",
    "ProjectRequest",
    "aggregate",
    "broadcast_partitions",
    "build_partitions",
    "combine_chunk_outputs",
    "filter_project",
    "groupby_sum",
    "hash_join",
    "hash_join_xla",
    "probe_vmem_footprint_bytes",
    "project",
    "project_any",
    "project_multi",
    "project_multi_xla",
    "project_xla",
    "reduced_result_bytes",
    "request_intervals",
    "scan_multi",
    "scan_multi_chunked",
    "scan_multi_xla",
    "scan_shard",
    "scan_vmem_footprint_bytes",
    "union_geometry",
    "vmem_footprint_bytes",
]
