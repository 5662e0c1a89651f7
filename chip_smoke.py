"""Bring-up smoke test: the relational engine's served path on a TPU.

Drives ``QueryServer`` -> ``compile_plan`` -> ``RelationalMemoryEngine.
execute_many`` -> the Pallas ``scan_multi`` / ``hash_join`` kernels once, at a
chip-resident size, and checks every answer against a plain numpy oracle
built from the same seed (independent of ``src/repro``).

* Data: the paper's §6.2 relation — ``benchmark_schema(64, 4)``, 16 int32
  columns uniform in [-1000, 1000) — plus a 65,536-row build table with
  unique join keys.
* Tick 1: one mixed read tick — a two-column projection, a ``gt`` filter
  with projection, a filtered ``sum`` and ``avg``, a 16-group ``groupby``
  and a device hash join.
* Tick 2: an insert of 4,096 rows, an update and a delete, then the same
  reads again under the tick's MVCC snapshot.
* The join probe kernel alone on full-range int32 keys, payloads and
  timestamps, at the smoke's bucket capacity and with one crowded bucket.

Exact equality is required for projections, filter blocks and masks, join
outputs and the filtered count.  Sums and averages accumulate in float32 on the device
and are compared with a float64 oracle within ``SUM_RTOL`` (printed).
The run fails if any kernel fallback, shard retry or failover happened.

The last line of standard output is one JSON object naming the device; it
reads ``"ok": true`` only on a TPU, after every check passed.

Run::

    python chip_smoke.py                  # one chip (what CI on the chip runs)
    python chip_smoke.py --chips 4        # sharded path on a 4-chip mesh only
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 4096   # CPU rehearsal; exits 1

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its compile cache there;
otherwise the script uses ``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

# 2^23 rows of 72 stored bytes: a 576 MiB row store, far above VMEM and the
# engine's 2 MB caches; float32 counts stay exact (below 2^24) after inserts
DEFAULT_ROWS = 1 << 23
BUILD_ROWS = 1 << 16
INSERT_ROWS = 4096
UPDATE_ROWS = 1024
DELETE_ROWS = 1024
NUM_GROUPS = 16
FILTER_K = 250  # filter: A3 > 250
AGG_K = 0  # aggregates: A4 < 0
# float32 accumulation over ~2^23 terms of |v| < 1000 against a float64 oracle
SUM_RTOL = 1e-4


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help=f"rows of the probe relation (default {DEFAULT_ROWS}); "
                         "also allows a CPU rehearsal, which still exits 1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a 4-device mesh")
    return ap.parse_args(argv)


def configure_compile_cache() -> None:
    """JAX_COMPILATION_CACHE_DIR wins; otherwise a fixed in-checkout path."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))


class CompileClock:
    """Sums JAX's own compile-duration events (tracing, lowering, backend)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


# ------------------------------------------------------------------ data
def make_columns(rng, n: int) -> dict:
    """Benchmark relation columns, as ``benchmarks/common.py`` draws them."""
    block = rng.integers(-1000, 1000, (16, n), dtype=np.int32)
    return {f"A{i + 1}": block[i] for i in range(16)}


def make_build_columns(rng) -> dict:
    cols = make_columns(rng, BUILD_ROWS)
    # unique even keys: about half the probe keys (uniform ints) find a match
    cols["A2"] = rng.permutation(np.arange(-BUILD_ROWS, BUILD_ROWS, 2,
                                           dtype=np.int32))
    return cols


class Oracle:
    """Plain numpy model of the probe table: physical rows in append order
    plus MVCC visibility.  Independent of ``src/repro``."""

    def __init__(self, cols: dict, build: dict):
        self.cols = {k: v.copy() for k, v in cols.items()}
        self.visible = np.ones(len(cols["A1"]), bool)
        order = np.argsort(build["A2"])
        self._build_keys = build["A2"][order]
        self._build_payload = build["A3"][order]

    def append(self, cols: dict) -> None:
        for k in self.cols:
            self.cols[k] = np.concatenate([self.cols[k], cols[k]])
        self.visible = np.concatenate(
            [self.visible, np.ones(len(cols["A1"]), bool)])

    def update(self, rows: np.ndarray, values: dict) -> None:
        new = {k: v[rows].copy() for k, v in self.cols.items()}
        new.update({k: v.copy() for k, v in values.items()})
        self.visible[rows] = False
        self.append(new)

    def delete(self, rows: np.ndarray) -> None:
        self.visible[rows] = False

    # --- expected answers
    def project(self, pinned: bool):
        packed = np.stack([self.cols["A1"], self.cols["A2"]], axis=1)
        if not pinned:
            return packed
        # a pinned projection zeroes the rows its snapshot cannot see
        return np.where(self.visible[:, None], packed, 0), self.visible.copy()

    def filter(self, pinned: bool):
        mask = self.cols["A3"] > FILTER_K
        if pinned:
            mask = mask & self.visible
        packed = np.stack([self.cols["A1"], self.cols["A2"]], axis=1)
        return np.where(mask[:, None], packed, 0), mask

    def agg_rows(self) -> np.ndarray:
        return (self.cols["A4"] < AGG_K) & self.visible

    def sum(self) -> float:
        return float(self.cols["A1"][self.agg_rows()].astype(np.float64).sum())

    def avg(self) -> float:
        rows = self.agg_rows()
        return float(self.cols["A1"][rows].astype(np.float64).sum()
                     / max(int(rows.sum()), 1))

    def count(self) -> int:
        return int(self.agg_rows().sum())

    def groupby_sums(self) -> np.ndarray:
        g = np.mod(self.cols["A5"].astype(np.int64), NUM_GROUPS)
        v = self.visible
        return np.bincount(g[v], weights=self.cols["A1"][v].astype(np.float64),
                           minlength=NUM_GROUPS)

    def join(self, pinned: bool):
        keys = self.cols["A2"]
        at = np.minimum(np.searchsorted(self._build_keys, keys),
                        len(self._build_keys) - 1)
        matched = self._build_keys[at] == keys
        valid = self.visible if pinned else np.ones_like(matched)
        matched = matched & valid
        return (np.where(valid, self.cols["A1"], 0),
                np.where(matched, self._build_payload[at], 0), matched)


# ------------------------------------------------------------------ checks
class Checker:
    def __init__(self):
        self.failures: list[str] = []
        self.passed = 0

    def exact(self, name: str, got, want) -> None:
        got, want = np.asarray(got), np.asarray(want)
        ok = got.shape == want.shape and np.array_equal(got, want)
        self.record(name, ok, f"shape {got.shape} vs {want.shape}" if
                    got.shape != want.shape else
                    f"{int((got != want).sum())} elements differ")

    def close(self, name: str, got: float, want: float) -> None:
        err = abs(got - want) / max(abs(want), 1.0)
        self.record(name, err <= SUM_RTOL,
                    f"got {got!r}, want {want!r}, rel err {err:.3g}")

    def record(self, name: str, ok: bool, detail: str) -> None:
        if ok:
            self.passed += 1
        else:
            self.failures.append(f"{name}: {detail}")
            print(f"FAIL {name}: {detail}", flush=True)


def submit_reads(server, table, build):
    from repro.core import plan

    return {
        "project": server.submit(plan(table).project("A1", "A2")),
        "filter": server.submit(
            plan(table).filter("A3", "gt", FILTER_K).project("A1", "A2")),
        "sum": server.submit(plan(table).filter("A4", "lt", AGG_K).sum("A1")),
        "avg": server.submit(plan(table).filter("A4", "lt", AGG_K).avg("A1")),
        "count": server.submit(
            plan(table).filter("A4", "lt", AGG_K).count("A1")),
        "groupby_sum": server.submit(
            plan(table).groupby("A5", "A1", "sum", NUM_GROUPS)),
        "join": server.submit(plan(table).join(
            build, key="A2", left_proj="A1", right_proj="A3")),
    }


def host(x):
    """Pull one ticket's device result(s) to numpy (a join's
    ``JoinResult`` becomes a dict of its fields)."""
    import jax

    if dataclasses.is_dataclass(x):
        x = {f.name: getattr(x, f.name) for f in dataclasses.fields(x)}
    return jax.tree.map(np.asarray, x)


def check_reads(chk: Checker, tag: str, results: dict, oracle: Oracle,
                pinned: bool) -> None:
    proj = results["project"]
    want = oracle.project(pinned)
    if pinned:
        chk.exact(f"{tag}/project.packed", proj[0], want[0])
        chk.exact(f"{tag}/project.mask", proj[1], want[1])
    else:
        chk.exact(f"{tag}/project", proj, want)
    fpacked, fmask = results["filter"]
    wpacked, wmask = oracle.filter(pinned)
    chk.exact(f"{tag}/filter.packed", fpacked, wpacked)
    chk.exact(f"{tag}/filter.mask", fmask, wmask)
    chk.close(f"{tag}/sum", float(results["sum"]), oracle.sum())
    chk.close(f"{tag}/avg", float(results["avg"]), oracle.avg())
    chk.exact(f"{tag}/count", np.asarray(results["count"], np.float64),
              np.float64(oracle.count()))
    sums = oracle.groupby_sums()
    got = np.asarray(results["groupby_sum"], np.float64)
    for g in range(NUM_GROUPS):
        chk.close(f"{tag}/groupby_sum[{g}]", float(got[g]), float(sums[g]))
    j = results["join"]
    ws, wr, wm = oracle.join(pinned)
    chk.exact(f"{tag}/join.s_proj", j["s_proj"], ws)
    chk.exact(f"{tag}/join.r_proj", j["r_proj"], wr)
    chk.exact(f"{tag}/join.matched", j["matched"], wm)


def run_ticks(server, table, build, oracle: Oracle | None, chk: Checker,
              rng_writes, label: str) -> list[dict]:
    """Tick 1 (mixed reads) and tick 2 (writes + the same reads), each
    checked against the oracle; returns each tick's host results."""
    out = []
    t0 = time.perf_counter()
    tickets = submit_reads(server, table, build)
    server.drain()
    r1 = {k: host(t.result()) for k, t in tickets.items()}
    print(f"{label} tick 1 wall s (smoke timing, not a benchmark): "
          f"{time.perf_counter() - t0}", flush=True)
    if oracle is not None:
        check_reads(chk, f"{label}/tick1", r1, oracle, pinned=False)
    out.append(r1)

    ins, upd_rows, upd_vals, del_rows = rng_writes
    t0 = time.perf_counter()
    w = [server.submit_insert(table, ins),
         server.submit_update(table, upd_rows, upd_vals),
         server.submit_delete(table, del_rows)]
    tickets = submit_reads(server, table, build)
    server.drain()
    for t in w:
        t.result()
    r2 = {k: host(t.result()) for k, t in tickets.items()}
    print(f"{label} tick 2 wall s (smoke timing, not a benchmark): "
          f"{time.perf_counter() - t0}", flush=True)
    if oracle is not None:
        oracle.append(ins)
        oracle.update(upd_rows, upd_vals)
        oracle.delete(del_rows)
        check_reads(chk, f"{label}/tick2", r2, oracle, pinned=True)
    out.append(r2)
    return out


def make_writes(seed: int, n: int):
    rng = np.random.default_rng(seed + 1)
    ins = make_columns(rng, INSERT_ROWS)
    picked = rng.choice(n, UPDATE_ROWS + DELETE_ROWS, replace=False)
    upd_rows, del_rows = picked[:UPDATE_ROWS], picked[UPDATE_ROWS:]
    upd_vals = {"A1": rng.integers(-1000, 1000, UPDATE_ROWS, dtype=np.int32),
                "A3": rng.integers(-1000, 1000, UPDATE_ROWS, dtype=np.int32)}
    return ins, np.sort(upd_rows), upd_vals, np.sort(del_rows)


def counters(server) -> dict:
    snap = server.snapshot()
    return {k: snap[k] for k in (
        "engine_kernel_fallbacks", "shared_pass_fallbacks", "retries",
        "engine_retries", "engine_failovers", "breaker_trips",
        "breaker_fallbacks", "failed", "poisoned")}


def check_counters(chk: Checker, label: str, server) -> None:
    c = counters(server)
    print(f"{label} counters: {json.dumps(c)}", flush=True)
    for k, v in c.items():
        chk.record(f"{label}/{k}", v == 0, f"{k} = {v}, want 0")


def check_join_words(chk: Checker, seed: int, dev) -> None:
    """The join probe kernel alone on full-range int32 words: keys,
    payloads and build timestamps drawn over all 32 bits (the relation's
    [-1000, 1000) values leave the high bytes all zeros or all ones), at
    the smoke's bucket capacity and with one crowded bucket, unpinned and
    pinned.  Every output must equal a numpy oracle bit for bit."""
    from repro.kernels import common
    from repro.kernels import rme_join as KJ

    i32 = np.iinfo(np.int32)
    rng = np.random.default_rng(seed + 1)
    limit = (common.vmem_limit_bytes(dev.device_kind)
             if dev.platform == "tpu" else None)

    def words(n, low=i32.min, high=i32.max):
        return rng.integers(low, high, n, dtype=np.int64).astype(np.int32)

    ts = 5
    for crowd in (0, 48):
        key = np.unique(words(BUILD_ROWS))
        p = KJ.num_buckets_for(key.shape[0])
        if crowd:  # extra keys that all hash to bucket 7
            pool = np.setdiff1d(words(1 << 22), key)
            key = np.concatenate(
                [key, pool[KJ.bucket_of_np(pool, p) == 7][:crowd]])
        m = key.shape[0]
        val = words(m)
        begin = np.where(rng.random(m) < 0.7, words(m, high=ts + 1),
                         words(m, low=ts + 1))
        end = np.where(rng.random(m) < 0.3, words(m, high=ts + 1),
                       words(m, low=ts + 1))
        parts = KJ.build_partitions(key, val, begin, end)
        s_key = np.where(rng.random(1 << 18) < 0.5, rng.choice(key, 1 << 18),
                         words(1 << 18))
        s_val = words(1 << 18)
        order = np.argsort(key)
        at = np.minimum(np.searchsorted(key[order], s_key), m - 1)
        hit = key[order][at] == s_key
        probe = np.stack([s_val, s_key], axis=1)
        for pinned in (False, True):
            want_m = hit & (((begin <= ts) & (ts < end))[order][at]
                            if pinned else True)
            want = (s_val, np.where(want_m, val[order][at], 0), want_m)
            got = KJ.hash_join(probe, parts, 1, 0, ts=ts, build_ts=pinned,
                               vmem_limit=limit)
            tag = f"join_words/C{parts.capacity}/{'pinned' if pinned else 'unpinned'}"
            for name, g, w in zip(("s_proj", "r_proj", "matched"), got, want):
                chk.exact(f"{tag}.{name}", g, w)


# ------------------------------------------------------------------ paths
def one_chip(args, n: int, chk: Checker, dev) -> None:
    from repro.core import RelationalMemoryEngine, RelationalTable, benchmark_schema
    from repro.serve import QueryServer

    schema = benchmark_schema(64, 4)
    rng = np.random.default_rng(args.seed)
    cols = make_columns(rng, n)
    build_cols = make_build_columns(rng)
    table = RelationalTable.from_columns(schema, cols)
    build = RelationalTable.from_columns(schema, build_cols)
    oracle = Oracle(cols, build_cols)
    del cols

    engine = RelationalMemoryEngine(revision="mlp")
    server = QueryServer(engine)
    print(f"engine.interpret: {engine.interpret}", flush=True)
    chk.record("engine.interpret", engine.interpret is (dev.platform != "tpu"),
               f"interpret={engine.interpret} on {dev.platform}")
    run_ticks(server, table, build, oracle, chk, make_writes(args.seed, n),
              "1chip")
    print(f"last_block_rows: {engine.stats.last_block_rows}", flush=True)
    print(f"row store bytes resident (logical): "
          f"{engine.rowstore.occupancy_bytes}", flush=True)
    stats = dev.memory_stats() or {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if key in stats:
            print(f"device {key}: {stats[key]}", flush=True)
    check_counters(chk, "1chip", server)
    check_join_words(chk, args.seed, dev)


def four_chips(args, n: int, chk: Checker) -> None:
    import jax

    from repro.core import RelationalMemoryEngine, RelationalTable, benchmark_schema
    from repro.launch.mesh import make_mesh
    from repro.serve import QueryServer

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = make_mesh((4,), ("data",))
    schema = benchmark_schema(64, 4)

    def tables():
        rng = np.random.default_rng(args.seed)
        cols = make_columns(rng, n)
        build_cols = make_build_columns(rng)
        return (RelationalTable.from_columns(schema, cols),
                RelationalTable.from_columns(schema, build_cols),
                cols, build_cols)

    s_table, s_build, cols, build_cols = tables()
    oracle = Oracle(cols, build_cols)
    del cols
    sharded = QueryServer(mesh=mesh)
    print(f"sharded engine: {sharded.engine.num_shards} shards, "
          f"interpret={sharded.engine.interpret}", flush=True)
    writes = make_writes(args.seed, n)
    got = run_ticks(sharded, s_table, s_build, oracle, chk, writes, "4chip")

    placed = [
        {d for c in chunks for d in c.words.devices()}
        for chunks in sharded.engine.rowstore.shard_parts(s_table)
    ]
    per_dev = [sum(c.words.size * c.words.dtype.itemsize for c in chunks)
               for chunks in sharded.engine.rowstore.shard_parts(s_table)]
    print(f"shard devices: {[sorted(str(d) for d in p) for p in placed]}",
          flush=True)
    print(f"row store bytes resident per device (logical): {per_dev}",
          flush=True)
    flat = [d for p in placed for d in p]
    chk.record("4chip/shards_on_distinct_devices",
               all(len(p) == 1 for p in placed) and len(set(flat)) == 4,
               f"placement {placed}")
    print(f"bytes_collective: {sharded.engine.stats.bytes_collective}",
          flush=True)
    check_counters(chk, "4chip", sharded)

    r_table, r_build, _, _ = tables()
    single = QueryServer(RelationalMemoryEngine(revision="mlp"))
    want = run_ticks(single, r_table, r_build, None, chk, writes,
                     "single-device reference")
    for t, (a, b) in enumerate(zip(got, want), start=1):
        for name in a:
            la, lb = jax.tree.leaves(a[name]), jax.tree.leaves(b[name])
            same = len(la) == len(lb) and all(
                x.dtype == y.dtype and x.shape == y.shape
                and x.tobytes() == y.tobytes() for x, y in zip(la, lb))
            chk.record(f"4chip/tick{t}/{name}==single", same,
                       "sharded result differs from single-device bytes")
    check_counters(chk, "single-device reference", single)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        sys.path.insert(0, str(ROOT / "src"))
        import repro.core  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: cannot import the engine ({e}); run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    import jax

    configure_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    if not on_tpu and args.rows is None:
        print("chip_smoke: no TPU found (pass --rows N to rehearse on this "
              "backend; the run still exits 1)", file=sys.stderr)
        return 1
    n = DEFAULT_ROWS if args.rows is None else args.rows
    print(f"rows: {n} (+{INSERT_ROWS} inserted, {UPDATE_ROWS} updated, "
          f"{DELETE_ROWS} deleted in tick 2); build rows: {BUILD_ROWS}; "
          f"seed: {args.seed}; sum/avg rtol: {SUM_RTOL}", flush=True)
    clock = CompileClock()
    chk = Checker()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, n, chk)
    else:
        one_chip(args, n, chk, dev)
    print(f"compile s: {clock.seconds}; total wall s: "
          f"{time.perf_counter() - t0}", flush=True)
    print(f"checks passed: {chk.passed}, failed: {len(chk.failures)}",
          flush=True)
    if chk.failures or not on_tpu:
        if not on_tpu:
            print("chip_smoke: not a TPU — rehearsal only, exiting 1",
                  file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
