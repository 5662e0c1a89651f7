"""Multi-device tests (subprocess with forced host device count).

The dry-run env var is process-local by design (tests/benches see 1 device),
so every multi-device scenario runs in a child interpreter with its own
``--xla_force_host_platform_device_count``.
"""

import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_distributed_relational_operators():
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import RelationalTable, benchmark_schema, TableGeometry
        from repro.core import distributed as D
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(2)
        schema = benchmark_schema(64, 4)
        n = 1003  # deliberately not divisible by 8: padding must be masked
        cols = {f"A{i+1}": rng.integers(-100, 100, n).astype(np.int32) for i in range(16)}
        t = RelationalTable.from_columns(schema, cols)
        mesh = make_mesh((8,), ("data",))
        words = D.pad_rows_to(t.words(), 8)
        geom = TableGeometry.from_schema(schema, ["A1", "A5"], row_count=n)

        out = np.asarray(D.dist_project(words, geom, mesh, valid_rows=n))
        ref = np.stack([cols["A1"], cols["A5"]], 1)
        np.testing.assert_array_equal(out[:n], ref)
        assert (out[n:] == 0).all(), "padding rows leaked into the packed output"

        agg = D.dist_aggregate(words, mesh, agg_word=0, pred_word=2,
                               pred_op="gt", pred_k=10, valid_rows=n)
        expect = cols["A1"][(cols["A3"] > 10)].sum()
        np.testing.assert_allclose(float(agg[0]), float(expect), rtol=1e-6)

        s, c = D.dist_groupby(words, mesh, group_word=1, agg_word=0,
                              num_groups=16, valid_rows=n)
        g = cols["A2"] % 16
        sr = np.zeros(16); np.add.at(sr, g, cols["A1"].astype(np.float64))
        np.testing.assert_allclose(np.asarray(s), sr, rtol=1e-5)
        print("OK")
    """)


def test_dist_join_padding_regression():
    """Padded rows carry key word 0; a legitimate key-0 build row must match
    real probes and never the padding (the pre-fix false-positive)."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import RelationalTable, benchmark_schema, TableGeometry
        from repro.core import distributed as D
        from repro.kernels.ref import hash_join_ref
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(5)
        schema = benchmark_schema(64, 4)
        n_s, n_r = 1001, 117  # both non-divisible by 8
        s_cols = {f"A{i+1}": rng.integers(-20, 20, n_s).astype(np.int32)
                  for i in range(16)}
        r_cols = {f"A{i+1}": rng.integers(-20, 20, n_r).astype(np.int32)
                  for i in range(16)}
        r_cols["A2"] = np.arange(n_r, dtype=np.int32) - 3  # unique keys incl. 0
        s_t = RelationalTable.from_columns(schema, s_cols)
        r_t = RelationalTable.from_columns(schema, r_cols)
        mesh = make_mesh((8,), ("data",))
        s_geom = TableGeometry.from_schema(schema, ["A1", "A2"], row_count=n_s)
        r_geom = TableGeometry.from_schema(schema, ["A2", "A3"], row_count=n_r)

        s_val, r_val, matched = D.dist_join(
            D.pad_rows_to(s_t.words(), 8), D.pad_rows_to(r_t.words(), 8),
            mesh, s_geom, r_geom, s_key_word=1, s_val_word=0,
            r_key_word=0, r_val_word=1, s_valid_rows=n_s, r_valid_rows=n_r,
        )
        s_val, r_val, matched = (np.asarray(s_val), np.asarray(r_val),
                                 np.asarray(matched))
        ref_s, ref_r, ref_m = hash_join_ref(
            jnp.asarray(s_cols["A2"]), jnp.asarray(s_cols["A1"]),
            jnp.asarray(r_cols["A2"]), jnp.asarray(r_cols["A3"]),
        )
        np.testing.assert_array_equal(matched[:n_s], np.asarray(ref_m))
        np.testing.assert_array_equal(r_val[:n_s], np.asarray(ref_r))
        np.testing.assert_array_equal(s_val[:n_s], np.asarray(ref_s))
        # key 0 exists on the build side, so some real probe matches it...
        assert matched[:n_s][s_cols["A2"] == 0].all()
        # ...but padded probe rows (also key 0) never match anything
        assert not matched[n_s:].any(), "padding probed the build side"
        print("OK")
    """)


def test_gpipe_pipeline_matches_sequential():
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.distributed.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("pod", "data"))
        n_stages, n_micro, d = 4, 8, 16
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.normal(0, 0.3, (n_stages, d, d)), jnp.float32)
        stage_fn = lambda w, x: jax.nn.relu(x @ w)
        pp = pipeline_apply(stage_fn, mesh, n_microbatches=n_micro, axis="pod")
        x = jnp.asarray(rng.normal(0, 1, (n_micro * 4, d)), jnp.float32)
        y = pp(ws, x)
        ref = x
        for i in range(n_stages):
            ref = jax.nn.relu(ref @ ws[i])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)
        print("OK")
    """)


def test_compressed_collectives():
    run_child("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import tree_psum_compressed
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = {"a": jnp.asarray(rng.normal(0, 1, (8, 32)), jnp.float32)}
        res = jax.tree.map(jnp.zeros_like, g)
        def red(mode):
            f = lambda gl, rl: tree_psum_compressed(gl, rl, "data", mode=mode)
            return jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                                 out_specs=(P("data"), P("data")))
        exact, _ = red("none")(g, res)
        bf, _ = red("bf16")(g, res)
        i8, r8 = red("int8_ef")(g, res)
        assert float(jnp.max(jnp.abs(exact["a"] - bf["a"]))) < 0.05
        assert float(jnp.max(jnp.abs(exact["a"] - i8["a"]))) < 0.5
        assert float(jnp.linalg.norm(r8["a"])) > 0  # error feedback captured
        print("OK")
    """)


def test_sharded_train_step_runs_and_matches_single_device():
    """Real (not dry) sharded train step on 8 devices == 1-device result."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_smoke_config
        from repro.distributed.partitioning import axis_rules, rules_for_mesh
        from repro.launch import specs as S
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.train import AdamWConfig, make_train_step
        from repro.train.step import init_train_state

        cfg = get_smoke_config("qwen3-8b")
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        model = build_model(cfg)
        rng = np.random.default_rng(0)
        B, S_ = 8, 64
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S_)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S_)), jnp.int32),
        }
        opt = AdamWConfig(lr=1e-3, warmup_steps=0)

        # single-device reference
        state = init_train_state(model, jax.random.PRNGKey(0))
        ref_state, ref_m = jax.jit(make_train_step(model, opt))(
            jax.tree.map(jnp.copy, state), batch)

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = rules_for_mesh(mesh)
        with axis_rules(rules, dict(zip(mesh.axis_names, mesh.devices.shape))), \\
             jax.sharding.set_mesh(mesh):
            state_sh = S.train_state_shardings(
                mesh, jax.eval_shape(lambda: state))
            batch_sh = S.batch_shardings(mesh, batch)
            state_d = jax.device_put(state, state_sh)
            batch_d = jax.device_put(batch, batch_sh)
            step = jax.jit(make_train_step(model, opt),
                           in_shardings=(state_sh, batch_sh),
                           out_shardings=(state_sh, None))
            new_state, m = step(state_d, batch_d)
        np.testing.assert_allclose(float(m["loss"]), float(ref_m["loss"]),
                                   rtol=1e-4)
        for a, b in zip(jax.tree.leaves(new_state["params"]),
                        jax.tree.leaves(ref_state["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=3e-4)
        print("OK")
    """, devices=8)


def test_sp_decode_matches_single_device():
    """Sequence-parallel decode (shard_map path) == unsharded decode."""
    run_child("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_smoke_config
        from repro.distributed.partitioning import axis_rules, rules_for_mesh
        from repro.launch import specs as S
        from repro.launch.mesh import make_mesh
        from repro.models import build_model

        cfg = get_smoke_config("qwen1.5-110b")
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        model = build_model(cfg)
        rng = np.random.default_rng(0)
        B, S_, max_len = 4, 32, 64
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S_)), jnp.int32)
        params = model.init(jax.random.PRNGKey(1))

        # unsharded reference
        logits0, cache0 = jax.jit(lambda p, b: model.prefill(p, b, max_len))(
            params, {"tokens": toks})
        step0 = jax.jit(model.decode_step)
        l_ref, _ = step0(params, cache0, jnp.argmax(logits0, -1)[:, None].astype(jnp.int32),
                         jnp.asarray(S_, jnp.int32))

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = rules_for_mesh(mesh)
        with axis_rules(rules, dict(zip(mesh.axis_names, mesh.devices.shape))), \\
             jax.sharding.set_mesh(mesh):
            logits1, cache1 = jax.jit(lambda p, b: model.prefill(p, b, max_len))(
                params, {"tokens": toks})
            l_sp, _ = jax.jit(model.decode_step)(
                params, cache1, jnp.argmax(logits1, -1)[:, None].astype(jnp.int32),
                jnp.asarray(S_, jnp.int32))
        np.testing.assert_allclose(np.asarray(l_sp), np.asarray(l_ref),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """, devices=8)


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Checkpoint on a (4,2) mesh, restore+step on (2,4) — elastic restart."""
    ckpt = str(tmp_path / "elastic")
    save_code = f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.distributed.partitioning import axis_rules, rules_for_mesh
        from repro.launch import specs as S
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.train.step import init_train_state
        from repro.ckpt import save_checkpoint

        cfg = get_smoke_config("qwen3-8b")
        model = build_model(cfg)
        mesh = make_mesh((4, 2), ("data", "model"))
        rules = rules_for_mesh(mesh)
        with axis_rules(rules, dict(zip(mesh.axis_names, mesh.devices.shape))), \\
             jax.sharding.set_mesh(mesh):
            state = init_train_state(model, jax.random.PRNGKey(0))
            sh = S.train_state_shardings(mesh, jax.eval_shape(lambda: state))
            state = jax.device_put(state, sh)
            save_checkpoint({ckpt!r}, 3, state)
        print("SAVED")
    """
    restore_code = f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.distributed.partitioning import axis_rules, rules_for_mesh
        from repro.launch import specs as S
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.train import AdamWConfig, make_train_step
        from repro.train.step import init_train_state
        from repro.ckpt import restore_checkpoint

        cfg = get_smoke_config("qwen3-8b")
        model = build_model(cfg)
        mesh = make_mesh((2, 4), ("data", "model"))  # DIFFERENT topology
        rules = rules_for_mesh(mesh)
        with axis_rules(rules, dict(zip(mesh.axis_names, mesh.devices.shape))), \\
             jax.sharding.set_mesh(mesh):
            like = jax.eval_shape(
                lambda: init_train_state(model, jax.random.PRNGKey(0)))
            sh = S.train_state_shardings(mesh, like)
            step, state = restore_checkpoint({ckpt!r}, like, shardings=sh)
            assert step == 3, step
            rng = np.random.default_rng(0)
            batch = {{
                "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 64)), jnp.int32),
                "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 64)), jnp.int32),
            }}
            fn = jax.jit(make_train_step(model, AdamWConfig()),
                         in_shardings=(sh, None), out_shardings=(sh, None))
            state, m = fn(state, batch)
            assert np.isfinite(float(m["loss"]))
        print("RESTORED+STEPPED on", mesh.devices.shape)
    """
    assert "SAVED" in run_child(save_code, devices=8)
    assert "RESTORED" in run_child(restore_code, devices=8)


def test_dryrun_cell_on_tiny_mesh():
    """The dry-run driver machinery on an 8-device (2,2,2) multi-pod mesh."""
    run_child("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.configs.base import ShapeSpec
        from repro.distributed.partitioning import axis_rules, rules_for_mesh
        from repro.launch import specs as S
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.train import AdamWConfig, make_train_step
        from repro.roofline.analysis import analyze_compiled

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = rules_for_mesh(mesh)
        cfg = get_smoke_config("gemma3-27b")
        sh = ShapeSpec("t", 128, 8, "train")
        model = build_model(cfg)
        with axis_rules(rules, dict(zip(mesh.axis_names, mesh.devices.shape))), \\
             jax.sharding.set_mesh(mesh):
            st = S.train_state_shapes(model, cfg)
            lowered = jax.jit(
                make_train_step(model, AdamWConfig(), grad_accum=2),
                in_shardings=(S.train_state_shardings(mesh, st),
                              S.batch_shardings(mesh, S.train_batch_shapes(cfg, sh))),
                out_shardings=(S.train_state_shardings(mesh, st), None),
            ).lower(st, S.train_batch_shapes(cfg, sh))
            compiled = lowered.compile()
        res = analyze_compiled(compiled, arch="gemma3-smoke", shape="t",
                               mesh_name="2x2x2", n_devices=8, model_flops=1e9)
        t = res.terms()
        assert all(v > 0 for v in t.values()), t
        assert res.collective["total"] > 0
        print("OK", t)
    """, devices=8)


# ===================================================== sharded backend (logical)
# The sharded engine's code path is device-count-independent: ``num_shards``
# without a mesh runs every shard on the current device, so the equality
# suite runs in-process (1 device) and the mesh placement runs in a child.

def _sharded_case(seed=7, n=1003, n_extra=37):
    import numpy as np
    from repro.core import benchmark_schema

    rng = np.random.default_rng(seed)
    schema = benchmark_schema(64, 4)
    # bounded int values: every partial sum is exactly representable in
    # float32, so re-associated sharded reductions are bit-equal
    cols = {c.name: rng.integers(-50, 50, n).astype(np.int32)
            for c in schema.columns}
    extra = {c.name: rng.integers(-50, 50, n_extra).astype(np.int32)
             for c in schema.columns}
    return schema, cols, extra


def _mk_ops(engine, t, r_t, snapshot_ts=None):
    from repro.core.requests import (
        AggregateOp, FilterOp, GroupByOp, JoinOp, ProjectOp,
    )

    return [
        ProjectOp(engine.register(t, ("A1", "A2"))),
        FilterOp(engine.register(t, ("A1", "A3")), "A3", "gt", 5,
                 snapshot_ts=snapshot_ts),
        AggregateOp(t, "A1", pred_col="A2", pred_op="lt", pred_k=0,
                    snapshot_ts=snapshot_ts),
        GroupByOp(t, "A2", "A1", 16, snapshot_ts=snapshot_ts),
        JoinOp(engine.register(t, ("A1", "A4")), "A1", "A4", r_t, "A3",
               snapshot_ts=snapshot_ts),
    ]


def _flatten(result):
    import numpy as np
    from repro.core.requests import JoinResult

    if isinstance(result, JoinResult):
        return [np.asarray(result.s_proj), np.asarray(result.r_proj),
                np.asarray(result.matched)]
    if isinstance(result, tuple):
        return [np.asarray(x) for x in result]
    return [np.asarray(result)]


def _assert_results_equal(a, b, label):
    import numpy as np

    for i, (x, y) in enumerate(zip(a, b)):
        for xa, ya in zip(_flatten(x), _flatten(y)):
            np.testing.assert_array_equal(xa, ya, err_msg=f"{label} op {i}")


def test_sharded_engine_matches_single_device():
    """Byte-identical results for every op kind, with and without a
    snapshot, across shard counts and revisions, on a non-divisible table."""
    import numpy as np
    from repro.core import RelationalMemoryEngine, RelationalTable
    from repro.core.distributed import ShardedEngine

    schema, cols, extra = _sharded_case()
    rng_r = np.random.default_rng(11)
    r_cols = {c.name: rng_r.integers(-50, 50, 130).astype(np.int32)
              for c in schema.columns}
    r_cols["A1"] = np.arange(130, dtype=np.int32) - 7  # unique keys incl. 0

    def run(engine, snapshot):
        t = RelationalTable.from_columns(
            schema, {k: v.copy() for k, v in cols.items()})
        r_t = RelationalTable.from_columns(
            schema, {k: v.copy() for k, v in r_cols.items()})
        ts = t.now() if snapshot else None
        return engine.execute_many(_mk_ops(engine, t, r_t, snapshot_ts=ts))

    for revision in ("xla", "mlp"):
        for snapshot in (False, True):
            ref = run(RelationalMemoryEngine(revision=revision), snapshot)
            for shards in (3, 4):
                got = run(ShardedEngine(num_shards=shards, revision=revision),
                          snapshot)
                _assert_results_equal(
                    ref, got, f"{revision} snap={snapshot} shards={shards}")


def test_sharded_mixed_tick_one_fused_pass_per_shard(monkeypatch):
    """A mixed-kind tick launches exactly one fused scan_multi per shard."""
    from repro.core import RelationalTable
    from repro.core.distributed import ShardedEngine
    from repro.core.plan import plan
    from repro.kernels import rme_scan_multi as KR
    from repro.serve.query_server import QueryServer

    schema, cols, _ = _sharded_case()
    t = RelationalTable.from_columns(schema, cols)
    engine = ShardedEngine(num_shards=4, revision="xla")
    server = QueryServer(engine, snapshot_reads=False)

    calls = []
    orig = KR.scan_multi

    def spy(words, requests, **kw):
        calls.append((words.shape[0], len(tuple(requests))))
        return orig(words, requests, **kw)

    monkeypatch.setattr(KR, "scan_multi", spy)
    for q in (plan(t).project("A1", "A2"),
              plan(t).aggregate("A1", "sum"),
              plan(t).groupby("A2", "A1", "sum", num_groups=8)):
        server.submit(q)
    server.run_tick()
    assert len(calls) == 4, calls  # one fused pass per shard, nothing else
    assert all(n_req == 3 for _, n_req in calls), calls
    assert sum(rows for rows, _ in calls) == t.row_count
    assert engine.stats.shared_scans == 1
    snap = server.snapshot()
    assert snap["engine_collective_ops"] == 2  # aggregate + group-by combines
    assert snap["engine_bytes_collective"] == 3 * (8 + 8 * 2 * 4)


def test_sharded_append_lands_only_in_owning_shard():
    """An append uploads O(new rows) bytes to exactly one shard's chunks."""
    from repro.core import RelationalTable
    from repro.core.distributed import ShardedEngine
    from repro.core.requests import AggregateOp

    schema, cols, extra = _sharded_case()
    t = RelationalTable.from_columns(schema, cols)
    engine = ShardedEngine(num_shards=4, revision="xla")
    engine.execute_many([AggregateOp(t, "A1")])  # full upload
    before = [[c.segments for c in chunks]
              for chunks in engine.rowstore.shard_parts(t)]

    n0 = t.row_count
    t.append(extra)
    delta0 = engine.stats.bytes_uploaded_delta
    engine.execute_many([AggregateOp(t, "A1")])  # syncs the delta
    n_extra = len(next(iter(extra.values())))
    assert (engine.stats.bytes_uploaded_delta - delta0
            == n_extra * t.row_words * 4)
    after = [[c.segments for c in chunks]
             for chunks in engine.rowstore.shard_parts(t)]
    changed = [s for s in range(4) if after[s] != before[s]]
    assert len(changed) == 1, changed  # exactly one owning shard grew
    new_segs = [seg for segs in after[changed[0]] for seg in segs
                if segs not in before[changed[0]]]
    assert (n0, n_extra) in new_segs


def test_sharded_mvcc_snapshot_reads_under_concurrent_writes():
    """A pinned read is byte-identical across backends while writes land."""
    import numpy as np
    from repro.core import RelationalMemoryEngine, RelationalTable
    from repro.core.distributed import ShardedEngine
    from repro.core.requests import AggregateOp, FilterOp, GroupByOp

    schema, cols, extra = _sharded_case(seed=13)

    def run(engine):
        t = RelationalTable.from_columns(
            schema, {k: v.copy() for k, v in cols.items()})
        engine.execute_many([AggregateOp(t, "A1")])  # resident before writes
        ts = t.now()
        t.append({k: v.copy() for k, v in extra.items()})
        t.delete(np.arange(20))
        t.update(np.arange(30, 40),
                 {"A1": np.full(10, 7, np.int32)})
        pinned = engine.execute_many([
            AggregateOp(t, "A1", snapshot_ts=ts),
            GroupByOp(t, "A2", "A1", 8, snapshot_ts=ts),
            FilterOp(engine.register(t, ("A1", "A2")), "A2", "gt", 0,
                     snapshot_ts=ts),
        ])
        live = engine.execute_many([AggregateOp(t, "A1", snapshot_ts=t.now())])
        return pinned + live

    ref = run(RelationalMemoryEngine(revision="xla"))
    got = run(ShardedEngine(num_shards=4, revision="xla"))
    _assert_results_equal(ref, got, "mvcc-under-writes")


def test_sharded_reset_drops_broadcast_cache():
    import numpy as np
    from repro.core import RelationalTable
    from repro.core.distributed import ShardedEngine
    from repro.core.requests import JoinOp

    schema, cols, _ = _sharded_case()
    rng = np.random.default_rng(17)
    r_cols = {c.name: rng.integers(-50, 50, 64).astype(np.int32)
              for c in schema.columns}
    r_cols["A1"] = np.arange(64, dtype=np.int32)
    t = RelationalTable.from_columns(schema, cols)
    r_t = RelationalTable.from_columns(schema, r_cols)
    engine = ShardedEngine(num_shards=4, revision="xla")
    engine.execute_many(
        [JoinOp(engine.register(t, ("A1", "A4")), "A1", "A4", r_t, "A3")])
    assert engine._bcast_parts  # broadcast replicas cached
    ops0 = engine.stats.collective_ops
    engine.reset()
    assert not engine._bcast_parts
    # the next probe re-broadcasts (fresh build after reset)
    engine.execute_many(
        [JoinOp(engine.register(t, ("A1", "A4")), "A1", "A4", r_t, "A3")])
    assert engine.stats.collective_ops > ops0


def test_sharded_collective_bytes_scale_with_results_not_rows():
    """Interconnect bytes are a function of result size only: growing the
    table 4x leaves aggregate/group-by collective traffic unchanged."""
    import numpy as np
    from repro.core import RelationalTable, benchmark_schema
    from repro.core.distributed import ShardedEngine
    from repro.core.requests import AggregateOp, GroupByOp

    schema = benchmark_schema(64, 4)
    rng = np.random.default_rng(19)

    def collective_bytes(n):
        cols = {c.name: rng.integers(-50, 50, n).astype(np.int32)
                for c in schema.columns}
        t = RelationalTable.from_columns(schema, cols)
        engine = ShardedEngine(num_shards=4, revision="xla")
        engine.execute_many([AggregateOp(t, "A1"),
                             GroupByOp(t, "A2", "A1", 16)])
        assert engine.stats.bytes_from_dram > 0
        return engine.stats.bytes_collective, engine.stats.bytes_from_dram

    coll_small, dram_small = collective_bytes(500)
    coll_large, dram_large = collective_bytes(2000)
    assert dram_large > 3 * dram_small  # the scan itself does scale
    assert coll_large == coll_small  # the interconnect does not


def test_group_ids_agree_across_paths():
    """Hostile keys (negative, near-overflow) group identically on the
    fused kernel, the sharded engine, the oracle, and dist_groupby."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import RelationalMemoryEngine, RelationalTable, benchmark_schema
    from repro.core.distributed import ShardedEngine
    from repro.core.requests import GroupByOp
    from repro.kernels.common import group_ids
    from repro.kernels.ref import groupby_sum_ref

    schema = benchmark_schema(64, 4)
    n, G = 512, 16
    rng = np.random.default_rng(23)
    hostile = np.concatenate([
        rng.integers(-(2**31), 2**31 - 1, n - 8).astype(np.int32),
        np.asarray([0, -1, -16, 2**31 - 1, -(2**31), 17, -17, 5], np.int32),
    ])
    cols = {c.name: rng.integers(-10, 10, n).astype(np.int32)
            for c in schema.columns}
    cols["A2"] = hostile
    t1 = RelationalTable.from_columns(schema, {k: v.copy() for k, v in cols.items()})
    t2 = RelationalTable.from_columns(schema, {k: v.copy() for k, v in cols.items()})

    # the shared lowering is a floored modulo: always in [0, G)
    g = np.asarray(group_ids(jnp.asarray(hostile), G))
    assert ((g >= 0) & (g < G)).all()
    np.testing.assert_array_equal(g, np.mod(hostile.astype(np.int64), G))

    fused = RelationalMemoryEngine(revision="xla").execute_many(
        [GroupByOp(t1, "A2", "A1", G)])[0]
    sharded = ShardedEngine(num_shards=4, revision="xla").execute_many(
        [GroupByOp(t2, "A2", "A1", G)])[0]
    oracle = groupby_sum_ref(jnp.asarray(t1.words()), 1, 0, "int32", G)
    from repro.core import distributed as D
    from repro.launch.mesh import make_mesh

    dist = D.dist_groupby(jnp.asarray(t1.words()), make_mesh((1,), ("data",)),
                          group_word=1, agg_word=0, num_groups=G, valid_rows=n)
    for a, b in ((fused, sharded), (fused, oracle), (fused, dist)):
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_sharded_engine_on_mesh_matches_single_device():
    """The same backend on a real 8-device mesh: per-device placement plus
    byte-identical results through the QueryServer."""
    run_child("""
        import numpy as np, jax
        assert len(jax.devices()) == 8
        from repro.core import RelationalMemoryEngine, RelationalTable, benchmark_schema
        from repro.core.distributed import ShardedEngine
        from repro.core.plan import plan
        from repro.launch.mesh import make_mesh
        from repro.serve.query_server import QueryServer

        rng = np.random.default_rng(29)
        schema = benchmark_schema(64, 4)
        n = 1003
        cols = {c.name: rng.integers(-50, 50, n).astype(np.int32)
                for c in schema.columns}
        extra = {c.name: rng.integers(-50, 50, 21).astype(np.int32)
                 for c in schema.columns}

        def serve(server):
            t = RelationalTable.from_columns(
                schema, {k: v.copy() for k, v in cols.items()})
            tickets = [
                server.submit(plan(t).project("A1", "A2")),
                server.submit(plan(t).filter("A3", "gt", 3).aggregate("A1", "sum")),
                server.submit(plan(t).groupby("A2", "A1", "sum", num_groups=8)),
                server.submit_insert(t, extra),
                server.submit(plan(t).aggregate("A1", "count")),
            ]
            server.run_tick()
            return [tk.result(timeout=30) for tk in tickets], t

        mesh = make_mesh((8,), ("data",))
        ref_server = QueryServer(RelationalMemoryEngine(revision="xla"))
        sh_engine = ShardedEngine(mesh=mesh, revision="xla")
        sh_server = QueryServer(sh_engine)
        ref, _ = serve(ref_server)
        got, t = serve(sh_server)
        for i, (a, b) in enumerate(zip(ref, got)):
            fa = a if isinstance(a, tuple) else (a,)
            fb = b if isinstance(b, tuple) else (b,)
            for x, y in zip(fa, fb):
                assert np.array_equal(np.asarray(x), np.asarray(y)), f"query {i}"
        # every shard's buffers live on that shard's own device
        for s, chunks in enumerate(sh_engine.rowstore.shard_parts(t)):
            for c in chunks:
                assert {d.id for d in c.words.devices()} == {s}
        snap = sh_server.snapshot()
        assert snap["engine_bytes_collective"] > 0
        assert snap["engine_collective_ops"] > 0
        print("OK")
    """)


def test_sharded_encoded_columns_match_single_device():
    """Compressed execution on the sharded backend: predicate translation is
    shard-local, per-code group-by partials combine across shards before the
    dictionary remap, shared-dictionary join keys survive the build-side
    broadcast — all byte-identical to the single-device engine."""
    import strategies
    from repro.core import RelationalMemoryEngine
    from repro.core.distributed import ShardedEngine
    from repro.core.requests import AggregateOp, FilterOp, GroupByOp, JoinOp

    def run(engine, seed):
        (probe, build), _, _ = strategies.build_tables(seed)
        ops = [
            FilterOp(engine.register(probe, ("K", "V")), "K", "gt", 0),
            AggregateOp(probe, "F", pred_col="K", pred_op="lt", pred_k=3),
            GroupByOp(probe, "K", "V", 16),
            GroupByOp(probe, "S", "V", len(strategies.STRING_POOL)),
            JoinOp(engine.register(probe, ("V", "K")), "V", "K",
                   build, "B"),
        ]
        return engine.execute_many(ops), engine

    for revision, seed in (("xla", 4), ("xla", 9), ("mlp", 9)):
        ref_res, _ = run(RelationalMemoryEngine(revision=revision), seed)
        for shards in (3, 4):
            got, eng = run(
                ShardedEngine(num_shards=shards, revision=revision), seed)
            _assert_results_equal(
                ref_res, got, f"{revision} shards={shards} seed={seed}")
            # the narrow word budget is charged per shard-local chunk too
            assert eng.stats.bytes_saved_compression > 0
