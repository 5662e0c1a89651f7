"""Table 2 analogue: the RME's on-chip memory budget.

The paper reports FPGA area (BRAM 60.7% — the 2 MB SPMs dominate).  The TPU
adaptation's equivalent scarce resource is VMEM (~128 MB/core on v5e): we
report the modeled VMEM working set of each kernel revision across block
sizes, and the fraction of VMEM it occupies — the quantity that decides
whether the engine's tiles double-buffer cleanly.
"""

from repro.core import TableGeometry, benchmark_schema
from repro.kernels.common import VMEM_BYTES_BY_KIND
from repro.kernels.rme_project import vmem_footprint_bytes

from .common import emit

VMEM_BYTES = VMEM_BYTES_BY_KIND["TPU v5 lite"]


def run() -> None:
    schema = benchmark_schema(64, 4)
    geom = TableGeometry.from_schema(schema, ["A1", "A7", "A13"], 1 << 20)
    for rev in ("bsl", "pck", "mlp"):
        for block_rows in (256, 1024, 4096, 16384):
            b = vmem_footprint_bytes(geom, block_rows, rev)
            emit(
                f"table2/{rev}_block{block_rows}",
                0.0,  # structural metric, no wall time
                f"vmem_bytes={b},vmem_frac={b / VMEM_BYTES:.4f}",
            )
