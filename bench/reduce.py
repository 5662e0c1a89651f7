"""Metric arithmetic: percentiles, tick grouping, the bytes a tick needs, and
the reduction of a profiler trace to busy, kernel and glue time.

Everything here is plain arithmetic over numbers the harness recorded or the
trace holds, so tests check it on the CPU against a trace recorded on the
chip (``bench/fixtures``).  The rules, once for every later PR:

* Busy time is the union of the intervals of the device's operations inside
  the traced window; the idle share is one minus busy over the window.
* Kernel time is the summed device time of the Pallas kernels: the
  operations that are a Mosaic custom call (:data:`KERNEL_TARGET` in the
  operation's HLO text).  Glue time is the summed device time of every
  other operation (relayout copies, row-range slices, concatenations,
  partial combines).
* A tick is the set of reads one server tick admitted: equal
  ``admitted_at``.  What a tick needs from HBM is, per table it reads, the
  rows times 4 B times the union of the row words its reads reference, plus
  the bytes of the results it returns.  Reads whose route runs no RME kernel
  are left out; a route not listed here is an error.
"""

from __future__ import annotations

import dataclasses
import math
from collections import defaultdict

WORD = 4

# a device operation of the trace is named by its HLO text; a Pallas kernel
# compiled for the TPU is a custom call to this target
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

# planner routes (``ticket.route``) whose device work runs an RME kernel,
# and those whose does not (cache hits, host paths, constant answers)
KERNEL_ROUTES = frozenset({
    "fused-aggregate", "fused-groupby", "fused-filter", "snapshot-project",
    "rme", "stream-project", "device-hash-join"})
NO_KERNEL_ROUTES = frozenset({
    "hot", "row", "row-fallback", "host-row", "host-col", "const-empty",
    "shared-scan-join", "flipped-scan-join"})


def runs_kernel(route: str) -> bool:
    if route in KERNEL_ROUTES:
        return True
    if route in NO_KERNEL_ROUTES:
        return False
    raise ValueError(f"route {route!r} is not known to bench/reduce.py")


# ------------------------------------------------------------ percentiles
def percentile(values, q: float) -> float:
    """The ``q``-th percentile, interpolated between order statistics
    (``statistics.quantiles`` with the inclusive method)."""
    values = sorted(values)
    if not values:
        raise ValueError("percentile of no values")
    if len(values) == 1:
        return float(values[0])
    pos = (len(values) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(values) - 1)
    return float(values[lo] + (values[hi] - values[lo]) * (pos - lo))


# ------------------------------------------------------------ ticks
def group_ticks(reads) -> list[list]:
    """Reads grouped into the ticks that admitted them, in admission order."""
    ticks: dict[float, list] = defaultdict(list)
    for r in reads:
        ticks[r.admitted_at].append(r)
    return [ticks[t] for t in sorted(ticks)]


def tick_need_bytes(tick, probe_rows: int, build_rows: int) -> int:
    """HBM bytes one tick needs: per table, rows x 4 B x the union of the
    words its kernel-served reads reference, plus their results' bytes.
    Each read carries ``route``, ``probe_words``, ``build_words`` (sets of
    word indices, the two MVCC words included where the read is pinned)
    and ``result_bytes``."""
    probe: set = set()
    build: set = set()
    results = 0
    for r in tick:
        if not runs_kernel(r.route):
            continue
        probe |= r.probe_words
        build |= r.build_words
        results += r.result_bytes
    return (probe_rows * WORD * len(probe) + build_rows * WORD * len(build)
            + results)


# ------------------------------------------------------------ trace
@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """What the reduction reads of one profiler trace: each device's
    operations, and every host event (annotations and runtime events)."""

    device_ops: dict[str, list[Event]]
    host: list[Event]

    def annotation(self, name: str) -> Event:
        spans = [e for e in self.host if e.name == name]
        if len(spans) != 1:
            raise ValueError(f"want one {name!r} span in the trace, "
                             f"found {len(spans)}")
        return spans[0]


DEVICE_PLANE_PREFIX = "/device:TPU:"
ROOT_PLANE = DEVICE_PLANE_PREFIX + "0"  # the harness's devices[0]
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def load_trace(xspace) -> Trace:
    """Read a trace from a ``jax.profiler.ProfileData``."""
    device_ops: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in xspace.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops = [Event(e.name, e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == DEVICE_OPS_LINE
                   for e in line.events]
            device_ops[plane.name] = sorted(ops, key=lambda e: e.start_ns)
        elif plane.name == HOST_PLANE:
            host.extend(Event(e.name, e.start_ns, e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.duration_ns > 0)
    return Trace(device_ops, host)


def root_ops(trace: Trace) -> list[Event]:
    """The operations of the root chip, where a sharded engine gathers its
    row outputs: the plane named :data:`ROOT_PLANE`, in whatever order the
    trace lists its planes.  No operations where the trace has no device
    plane."""
    if not trace.device_ops:
        return []
    if ROOT_PLANE not in trace.device_ops:
        raise ValueError(f"no {ROOT_PLANE!r} among the trace's device "
                         f"planes {sorted(trace.device_ops)}")
    return trace.device_ops[ROOT_PLANE]


def _clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_t = 0.0, None, None
    for s, t in sorted(intervals):
        if cur_t is None or s > cur_t:
            if cur_t is not None:
                total += cur_t - cur_s
            cur_s, cur_t = s, t
        else:
            cur_t = max(cur_t, t)
    if cur_t is not None:
        total += cur_t - cur_s
    return total


def is_kernel(name: str) -> bool:
    return KERNEL_TARGET in name


def op_name(name: str) -> str:
    """An operation's instruction name without its number: ``%copy.12 =
    s32[...] copy(...)`` is ``copy``, the scan kernel ``_scan_multi``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


@dataclasses.dataclass
class DeviceTime:
    """Device time inside one window: ``busy_ns``, ``kernel_ns`` and
    ``glue_ns`` averaged over the chips used, and each chip's busy and
    kernel time apart, in device order: :data:`ROOT_PLANE` first, then
    ``/device:TPU:1`` and on (:func:`chip_planes`)."""

    window_ns: float
    busy_ns: float
    kernel_ns: float
    glue_ns: float
    busy_ns_per_chip: list[float] = dataclasses.field(default_factory=list)
    kernel_ns_per_chip: list[float] = dataclasses.field(default_factory=list)


def chip_planes(trace: Trace, chips: int) -> list[str]:
    """The device planes of a cell's ``chips`` chips, ``/device:TPU:0`` to
    ``/device:TPU:<chips - 1>``, the harness's ``devices[:chips]``.  The
    planes of other chips on the host are not the cell's and are left
    out; a trace that lacks one of the cell's planes is refused."""
    want = [f"{DEVICE_PLANE_PREFIX}{i}" for i in range(chips)]
    missing = [p for p in want if p not in trace.device_ops]
    if missing:
        raise ValueError(f"no {missing} among the trace's device planes "
                         f"{sorted(trace.device_ops)} for a cell of "
                         f"{chips} chips")
    return want


def device_time(trace: Trace, lo: float, hi: float, chips: int) -> DeviceTime:
    busy, kernel, glue = [], [], 0.0
    for plane in chip_planes(trace, chips):
        ops = trace.device_ops[plane]
        busy.append(union_ns(_clip(ops, lo, hi)))
        chip_kernel = 0.0
        for e in ops:
            d = max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
            if is_kernel(e.name):
                chip_kernel += d
            else:
                glue += d
        kernel.append(chip_kernel)
    return DeviceTime(hi - lo, sum(busy) / chips, sum(kernel) / chips,
                      glue / chips, busy, kernel)


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """The device operations that took most time in the window, by
    instruction name (:func:`op_name`)."""
    total: dict[str, float] = defaultdict(float)
    for ops in trace.device_ops.values():
        for e in ops:
            d = max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
            if d > 0:
                total[op_name(e.name)] += d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10,
              ignore: tuple[str, ...] = ()) -> list:
    """The longest idle gaps of the root chip in the window, each named
    by what the host was doing: the shortest host event that spans the
    gap's middle, else the one that overlaps it most (``ignore`` names
    spans to pass over, such as the benchmark's own around the window)."""
    if not trace.device_ops:
        return []
    gaps, cur = [], lo
    for s, t in sorted(_clip(root_ops(trace), lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [e for e in trace.host if e.name not in ignore]
    out = []
    for s, t in gaps:
        mid = (s + t) / 2
        spanning = [e for e in host if e.start_ns <= mid <= e.end_ns]
        if spanning:
            name = min(spanning, key=lambda e: e.dur_ns).name
        else:
            best = max(host, default=None,
                       key=lambda e: min(e.end_ns, t) - max(e.start_ns, s))
            name = (best.name if best is not None
                    and min(best.end_ns, t) > max(best.start_ns, s)
                    else "no host event")
        out.append([name, (t - s) * 1e-9])
    return out
