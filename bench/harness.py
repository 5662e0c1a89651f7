"""One run of one benchmark cell: set-up, measured window, reference check.

The cell, its configuration, its traffic mix and its metrics are all found
by name: the cell in ``BENCHMARK.json``, the configuration in
``bench/configs/<config>.json``, the mix in ``bench/mixes/<traffic>.json``,
the kind of client the mix names in ``bench/clients/<clients>.py``, each
metric's reader in ``bench/metrics/<metric>.py``.

A cell's ``chips`` picks the engine: on one chip the single-device
``RelationalMemoryEngine``; on more, a ``ShardedEngine`` that holds the
table as that many row ranges, one on each chip.  The result's
``memory_peak_bytes`` is the fullest chip's peak, with every chip's in
``memory_peak_bytes_per_chip``.

Every read goes the served path: ``QueryServer.submit`` on a server running
its own ``start()`` loop -> ``compile_plan`` -> ``execute_many`` -> the
Pallas kernels.  A read is timed from just before its submit until the
client holds its answer: a scalar or group vector as the ticket returns it,
a row output ready on the device (``block_until_ready``, not pulled to the
host).  The window closes ``--seconds`` after it opens: nothing is sent
after that, and what was sent before is waited for and counted.  A sample
of the window's answers, drawn from the seed, is compared with the plain
reference once the window has closed.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import importlib
import json
import os
import pathlib
import sys
import tempfile
import time

from bench import check, reduce, spans, traffic
from bench.peaks import peaks
from bench.reference import Reference, make_build_columns, make_columns

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"

TRACE_SECONDS = 3.0  # the traced run traces whole rounds for about this long
WAIT_PAST_CLOSE_S = 60.0  # how long a read may still answer after the window


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: traffic.Mix
    end_to_end: list[dict]
    per_layer: list[dict]

    @staticmethod
    def load(workload: str) -> "Cell":
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        w = cells[workload]
        config = json.loads(
            (BENCH / "configs" / f"{w['config']}.json").read_text())

        def mine(metrics):
            return [m for m in metrics
                    if workload in m.get("workloads", [workload])]

        return Cell(workload, w["chips"], config,
                    traffic.Mix.load(w["traffic"]),
                    mine(spec["end_to_end"]), mine(spec["per_layer"]))


@dataclasses.dataclass
class ReadRecord:
    """One read as the client saw it, with what the server stamped on its
    ticket."""

    read: traffic.Read
    t_submit: float
    t_ready: float | None = None
    admitted_at: float | None = None
    queue_wait_s: float | None = None
    route: str | None = None
    failed: bool = False
    result_bytes: int = 0
    probe_words: frozenset = frozenset()
    build_words: frozenset = frozenset()


@dataclasses.dataclass
class Traced:
    """The traced part of a ``--trace 1`` window."""

    device: reduce.DeviceTime
    ticks: int
    need_bytes: int
    hbm_bytes_per_s: float  # of every chip that holds a part of the table
    breakdown: dict
    # bench.spans.metrics: the idle split on the root chip, and every
    # serving-thread span's ``span_ms_per_tick`` and ``span_count_per_tick``
    spans: dict


@dataclasses.dataclass
class Window:
    """What the metric readers read."""

    setup_s: float
    seconds: float  # from the window's opening to its last answer
    reads: list[ReadRecord]  # every read sent in the window and answered
    engine_delta: dict[str, int]  # of every counter in engine_counters()
    traced: Traced | None = None


class Clock:
    """Counts the executables JAX obtained (compiled, or loaded from the
    persistent cache) and the seconds that took, and the cache hits."""

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self.names: list[str] = []  # of the programs obtained, in order
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, fun_name="?",
                     **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration
            self.names.append(fun_name)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def counts(self) -> tuple[int, int]:
        """(programs obtained, of which compiled rather than loaded)."""
        return self.programs, self.programs - self.cache_hits


def configure_compile_cache() -> None:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    a fixed directory in the checkout.  Every program is cached, however
    short its compile, so a later run of the cell compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def leaves(result) -> list:
    import jax

    if dataclasses.is_dataclass(result):
        result = tuple(getattr(result, f.name)
                       for f in dataclasses.fields(result))
    return jax.tree.leaves(result)


def nbytes(parts) -> int:
    return sum(getattr(x, "nbytes", 8) for x in parts)


def load_client(kind: str):
    """The ``Client`` class of ``bench/clients/<kind>.py``."""
    return importlib.import_module(f"bench.clients.{kind}").Client


# EngineStats fields that hold a last value, not a count: no window delta
ENGINE_GAUGES = frozenset({"last_block_rows"})


def engine_counters(engine) -> dict[str, int]:
    """Every counter of the engine's ``EngineStats``, by field name, so a
    reader of a counter needs no edit here."""
    return {f.name: getattr(engine.stats, f.name)
            for f in dataclasses.fields(engine.stats)
            if f.name not in ENGINE_GAUGES}


def _read_metric(name: str, window: Window):
    return importlib.import_module(f"bench.metrics.{name}").read(window)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: float, rows: int | None = None,
        require_accelerator: bool = True) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    ``t0`` is the process's start on the ``perf_counter`` clock.  ``rows``
    and ``require_accelerator=False`` let a CPU test drive the same run at
    a small size."""
    import jax

    cell = Cell.load(workload)
    configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    used = devices[:cell.chips]
    if len(used) < cell.chips or (require_accelerator
                                  and dev.platform != "tpu"):
        raise NoAccelerator(
            f"cell {workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {dev.platform} device(s)")
    peak = peaks(dev.device_kind) if dev.platform == "tpu" else None
    if trace and peak is None:
        raise NoAccelerator("a traced run reads device time: it needs a TPU")
    clock = Clock()

    from repro.core import RelationalTable, benchmark_schema
    from repro.serve import QueryServer

    cfg = cell.config
    n = cfg["rows"] if rows is None else rows
    bcfg = cfg["build"]
    split = {}
    t = time.perf_counter()
    cols = make_columns(traffic.rng(seed, traffic.STREAM_DATA), n,
                        cfg["columns"])
    bcols = make_build_columns(traffic.rng(seed, traffic.STREAM_BUILD),
                               bcfg["rows"], bcfg["columns"], bcfg["key"])
    split["data"] = time.perf_counter() - t

    t = time.perf_counter()
    schema = benchmark_schema(cfg["columns"] * cfg["column_bytes"],
                              cfg["column_bytes"])
    table = RelationalTable.from_columns(schema, cols)
    build = RelationalTable.from_columns(
        benchmark_schema(bcfg["columns"] * cfg["column_bytes"],
                         cfg["column_bytes"]), bcols)
    split["table_build"] = time.perf_counter() - t

    engine = make_engine(cfg, used)
    server = QueryServer(engine, **cfg["server"])
    t = time.perf_counter()
    jax.block_until_ready(resident(engine, table))
    split["upload"] = time.perf_counter() - t

    probe_word = {c.name: schema.word_offset(c.name) for c in schema.columns}
    build_word = {c.name: build.schema.word_offset(c.name)
                  for c in build.schema.columns}
    words = {}
    for tpl in cell.mix.templates:
        p, b = traffic.words_referenced(tpl, probe_word, build_word)
        words[tpl["name"]] = (frozenset(p), frozenset(b))

    client = load_client(cell.mix.clients)(server, table, build, words,
                                           cell.mix, WAIT_PAST_CLOSE_S)
    server.start()
    try:
        t = time.perf_counter()
        client.warm_up(~seed)
        print(f"warm-up: programs obtained, compiled: {clock.counts()}",
              file=sys.stderr, flush=True)
        split["compile_warm"] = time.perf_counter() - t
        setup_programs = clock.counts()
        split["of_which_obtaining_programs"] = clock.seconds
        gc.collect()
        setup_s = time.perf_counter() - t0
        print("setup_s split: " + json.dumps(split), file=sys.stderr,
              flush=True)

        result = _window(cell, client, engine, used, seed, seconds, trace,
                         n, bcfg["rows"], peak, setup_s)
    finally:
        client.stop()
        server.stop()
    programs = clock.counts()
    print(f"programs obtained, compiled: {setup_programs} in set-up, "
          f"{(programs[0] - setup_programs[0], programs[1] - setup_programs[1])}"
          f" in the window: {clock.names[setup_programs[0]:]}",
          file=sys.stderr, flush=True)

    peaks_per_chip = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": max(peaks_per_chip),
                        "memory_peak_bytes_per_chip": peaks_per_chip,
                        **result["device"]}
    samples = result.pop("samples")
    del client, server, engine, table, build
    gc.collect()
    numbers = check.compare(samples, Reference(cols, bcols, bcfg["key"]),
                            result["failed"])
    result["correct"] = check.verdict(numbers)
    result["checks"] = check.report(numbers)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks")
    return {k: result[k] for k in order if k in result}


def make_engine(cfg: dict, used: list):
    """The engine over the cell's chips ``used``: the single-device engine
    on one, else a ``ShardedEngine`` with one shard on each."""
    from repro.core import RelationalMemoryEngine
    from repro.core.distributed import ShardedEngine

    chips = len(used)
    if chips == 1:
        return RelationalMemoryEngine(**cfg["engine"])
    import numpy as np
    from jax.sharding import Mesh

    return ShardedEngine(mesh=Mesh(np.array(used), ("data",)),
                         num_shards=chips, **cfg["engine"])


def resident(engine, table) -> list:
    """The table's chunks as the engine keeps them on the device: every
    shard's own on its own chip for a sharded engine, nothing gathered."""
    from repro.core.distributed import ShardedEngine

    if not isinstance(engine, ShardedEngine):
        return list(engine.device_chunks(table))
    parts = engine.rowstore.shard_parts(table)
    print("upload: shard rows on devices " + json.dumps(
        [[c.rows, sorted(str(d) for d in c.words.devices())]
         for chunks in parts for c in chunks]), file=sys.stderr, flush=True)
    return [c.words for chunks in parts for c in chunks]


def _memory(devs) -> str:
    out = []
    for i, dev in enumerate(devs):
        mem = dev.memory_stats() or {}
        out.append(f"chip {i}: " + ", ".join(f"{k} {mem[k]}" for k in (
            "bytes_in_use", "largest_free_block_bytes") if k in mem))
    return "; ".join(out)


class _GcClock:
    """Python's garbage collections while it is installed: how many, and
    the seconds they took."""

    def __init__(self):
        self.count, self.seconds, self._t = 0, 0.0, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t
            self._t = None

    def remove(self):
        gc.callbacks.remove(self._on_gc)


def _window(cell: Cell, client, engine, devs, seed: int, seconds: float,
            trace: bool, probe_rows: int, build_rows: int, peak, setup_s):
    import jax

    mix = cell.mix
    points = mix.sample_points(seed)
    kept: dict[str, tuple] = {}
    records: list[ReadRecord] = []
    traced_recs: list[ReadRecord] = []
    before = engine_counters(engine)
    trace_dir = None
    gc_clock = _GcClock()
    client.start(seed)
    start = time.perf_counter()
    end = start + seconds
    trace_at = start + seconds / 3

    def step():
        t = time.perf_counter()
        keep = (lambda name: name not in kept
                and t >= start + points[name] * seconds)
        recs, answers = client.step(keep)
        for name, (read, answer) in answers.items():
            kept[name] = (read, check.host(answer))  # off the device at once
        records.extend(recs)
        print(f"step {len(steps)}: at {t - start} s, "
              f"{time.perf_counter() - t} s, {len(recs)} reads, kept "
              f"{sorted(answers)}; {_memory(devs)}", file=sys.stderr, flush=True)
        steps.append(t)
        return recs

    steps: list[float] = []
    while time.perf_counter() < end:
        if trace and trace_dir is None and time.perf_counter() >= trace_at:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # runtime and annotations only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t_trace = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.traced"):
                while (len(traced_recs) < 2 * len(mix.templates)
                       or time.perf_counter() - t_trace < TRACE_SECONDS):
                    traced_recs.extend(step())
            jax.profiler.stop_trace()
            continue
        step()
    gc_clock.remove()
    after = engine_counters(engine)

    answered = [r for r in records if r.t_ready is not None]
    if trace and trace_dir is None:
        raise RuntimeError("the window ended before the traced rounds began")
    span = max((r.t_ready for r in answered), default=end) - start
    window = Window(setup_s, span, answered,
                    {k: after[k] - before[k] for k in after})
    device = {}
    breakdown = None
    if trace:
        window.traced = _reduce_trace(trace_dir, traced_recs, cell.chips,
                                      probe_rows, build_rows, peak)
        dt = window.traced.device
        device = {"busy_s": dt.busy_ns * 1e-9, "window_s": dt.window_ns * 1e-9,
                  "busy_s_per_chip": [ns * 1e-9 for ns in dt.busy_ns_per_chip]}
        breakdown = window.traced.breakdown
    metrics = cell.per_layer if trace else cell.end_to_end
    values = {}
    for m in metrics:
        v = _read_metric(m["name"], window)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    for line in client.report():
        print(line, file=sys.stderr, flush=True)
    print(f"python gc in the window: {gc_clock.count} collections, "
          f"{gc_clock.seconds} s", file=sys.stderr, flush=True)
    by_template: dict[str, list[float]] = {}
    for r in answered:
        by_template.setdefault(r.read.name, []).append(r.t_ready - r.t_submit)
    print("median read ms by template: " + json.dumps(
        {k: reduce.percentile(v, 50) * 1e3 for k, v in by_template.items()}),
        file=sys.stderr, flush=True)
    ticks = reduce.group_ticks(answered)
    print(f"window: {len(answered)} reads answered, sent in {seconds} s and "
          f"answered in {span} s, {len(ticks)} ticks, routes "
          f"{sorted({r.route for r in answered if r.route})}, engine "
          f"counters {({k: v for k, v in window.engine_delta.items() if v})}",
          file=sys.stderr, flush=True)
    out = {"attempted": len(records),
           "failed": sum(r.failed for r in records),
           "metrics": values, "device": device,
           "samples": [kept[name] for name in sorted(kept)]}
    if breakdown is not None:
        out["breakdown"] = breakdown
    return out


def _reduce_trace(trace_dir, recs, chips, probe_rows, build_rows, peak) -> Traced:
    import shutil

    import jax

    try:
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"want one trace file, found {paths}")
        xspace = jax.profiler.ProfileData.from_file(paths[0])
        return reduce_traced(xspace, recs, chips, probe_rows, build_rows,
                             peak)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def reduce_traced(xspace, recs, chips, probe_rows, build_rows, peak) -> Traced:
    """What the readers read of one traced window.  Device time is the mean
    over the ``chips`` device planes; the table's need is met by all of
    them, so its bandwidth is ``chips`` times one chip's.  The program
    spans' idle split and the idle gaps are the root chip's
    (``reduce.ROOT_PLANE``)."""
    tr = reduce.load_trace(xspace)
    window = tr.annotation("bench.traced")
    lo, hi = window.start_ns, window.end_ns
    dt = reduce.device_time(tr, lo, hi, chips)
    done = [r for r in recs if r.t_ready is not None]
    ticks = reduce.group_ticks(done)
    need = sum(reduce.tick_need_bytes(t, probe_rows, build_rows)
               for t in ticks)
    if dt.kernel_ns == 0 and any(reduce.runs_kernel(r.route) for r in done):
        raise RuntimeError("reads were served by kernel routes but the trace "
                           "holds no kernel event: see bench/reduce.KERNEL_TARGET")
    breakdown = {
        "device_ops": reduce.top_ops(tr, lo, hi),
        "idle_gaps": reduce.idle_gaps(tr, lo, hi, ignore=("bench.traced",)),
    }
    span_metrics = spans.metrics(spans.serving_thread(xspace).spans.spans,
                                 spans.busy_intervals(tr, lo, hi), lo, hi,
                                 len(ticks))
    return Traced(dt, len(ticks), need, peak["hbm_bytes_per_s"] * chips,
                  breakdown, span_metrics)
