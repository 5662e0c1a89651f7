"""The roofline analyzer itself: trip-count weighting, wire model, dtypes.

The §Roofline numbers are only as good as this parser — verify it against
compiled programs with known FLOP/collective structure.
"""


import jax
import jax.numpy as jnp

from repro.roofline.analysis import (
    _dot_flops,
    _group_size,
    _wire_bytes,
    compiled_hlo_text,
    hlo_stats,
    roofline_terms,
)


def compile_fn(f, *shapes):
    return jax.jit(f).lower(*shapes).compile()


def test_scan_trip_count_weighting_exact():
    """A scanned matmul must count trip_count × one-matmul FLOPs, exactly."""
    for n in (1, 3, 10, 37):
        def f(x, n=n):
            def body(c, _):
                return c @ c, None
            y, _ = jax.lax.scan(body, x, None, length=n)
            return y

        c = compile_fn(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
        stats = hlo_stats(compiled_hlo_text(c))
        assert stats["flops"] == 2 * 128**3 * n, n
        assert stats["trip_weighted"]


def test_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            y, _ = jax.lax.scan(inner, c, None, length=4)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    c = compile_fn(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    stats = hlo_stats(compiled_hlo_text(c))
    assert stats["flops"] == 2 * 64**3 * 12  # 3 × 4 inner matmuls


def test_unscanned_matmul_baseline():
    c = compile_fn(lambda a, b: a @ b,
                   jax.ShapeDtypeStruct((64, 256), jnp.float32),
                   jax.ShapeDtypeStruct((256, 32), jnp.float32))
    stats = hlo_stats(compiled_hlo_text(c))
    assert stats["flops"] == 2 * 64 * 256 * 32


def test_dot_flops_parser_units():
    line = ("%dot.1 = f32[256,32]{1,0} dot(f32[256,512]{1,0} %a, "
            "f32[512,32]{1,0} %b), lhs_contracting_dims={1}, "
            "rhs_contracting_dims={0}")
    assert _dot_flops(line) == 2 * 256 * 32 * 512
    batched = ("%dot.2 = f32[8,64,32]{2,1,0} dot(f32[8,64,128]{2,1,0} %a, "
               "f32[8,128,32]{2,1,0} %b), lhs_batch_dims={0}, "
               "lhs_contracting_dims={2}, rhs_batch_dims={0}, "
               "rhs_contracting_dims={1}")
    assert _dot_flops(batched) == 2 * (8 * 64 * 32) * 128


def test_wire_model_units():
    ag = ("%ag = bf16[64,512]{1,0} all-gather(bf16[64,32]{1,0} %x), "
          "replica_groups=[4,16]<=[64], dimensions={1}")
    assert _group_size(ag) == 16
    assert _wire_bytes("all-gather", ag) == 64 * 512 * 2 * 15 // 16
    ar = ("%ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), "
          "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add")
    assert _group_size(ar) == 4
    assert _wire_bytes("all-reduce", ar) == 2 * 4096 * 3 // 4
    cp = ("%cp = bf16[256]{0} collective-permute(bf16[256]{0} %x), "
          "source_target_pairs={{0,1},{1,0}}")
    assert _wire_bytes("collective-permute", cp) == 512


def test_collectives_detected_in_compiled_program():
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.roofline.analysis import compiled_hlo_text, hlo_stats

        mesh = make_mesh((8,), ("data",))
        def f(x):
            return jax.lax.psum(x * 2, "data")
        c = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                  out_specs=P())).lower(
            jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
        stats = hlo_stats(compiled_hlo_text(c))
        coll = stats["collectives"]
        assert coll["all-reduce"] > 0, coll
        # per-chip shard is 128 floats = 512 B; ring all-reduce 2*(7/8)*512
        assert coll["all-reduce"] == 2 * 512 * 7 // 8, coll
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]


def test_roofline_terms_math():
    t = roofline_terms(197e12, 819e9, 50e9)
    assert abs(t["compute"] - 1.0) < 1e-9
    assert abs(t["memory"] - 1.0) < 1e-9
    assert abs(t["collective"] - 1.0) < 1e-9


def test_dus_scan_bytes_not_whole_buffer():
    """Scan ys-stacking must bill the slice, not the stacked buffer."""
    def f(x):
        def body(c, _):
            c = c + 1.0
            return c, c
        _, ys = jax.lax.scan(body, x, None, length=100)
        return ys

    c = compile_fn(f, jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
    stats = hlo_stats(compiled_hlo_text(c))
    buffer_bytes = 100 * 1024 * 1024 * 4
    # honest per-iteration traffic: carry read+write (8 MB), carry copy
    # (4 MB), add read+slice write (8 MB) ≈ 20 MB × 100 = 5× the stacked
    # buffer, plus its one-time zero-init (1×); some XLA versions emit one
    # more per-iteration carry copy (~8×).  Billing the whole buffer per
    # iteration (the naive parse) would be ~100×.
    assert stats["hbm_bytes"] < 10 * buffer_bytes, (
        stats["hbm_bytes"] / buffer_bytes
    )
    assert stats["hbm_bytes"] > 2 * buffer_bytes  # sanity floor
