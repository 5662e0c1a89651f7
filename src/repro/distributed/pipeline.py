"""GPipe pipeline parallelism over the ``pod`` axis (collective-permute ring).

The default dry-run folds ``pod`` into data parallelism (one code path for
all 40 cells); this module provides the alternative mapping where the two
pods form two pipeline stages.  Schedule: GPipe with M microbatches —
forward fills the ring stage by stage via ``ppermute``, activations flow
pod→pod over the (slow) inter-pod links exactly once per microbatch per
stage boundary, which is the property that makes PP attractive between pods:
O(activations) inter-pod traffic instead of O(gradients) for pure DP.

Implementation: ``shard_map`` over ``pod``; each stage holds its slice of
the stacked layer params; microbatches stream with a standard skew of
``n_stages - 1`` bubble steps.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    fn: Callable,  # (stage_params, x) -> x  : one stage's layer stack
    mesh: Mesh,
    n_microbatches: int,
    axis: str = "pod",
) -> Callable:
    """Wrap a per-stage function into a GPipe forward over ``axis``.

    ``stage_params`` must be sharded stage-major on dim 0 (P(axis, ...));
    ``x`` microbatched on dim 0 into ``n_microbatches`` slices, batch-sharded
    on nothing (each stage sees every microbatch in turn).
    """
    n_stages = mesh.shape[axis]

    def wrapped(stage_params, x):
        def local(params_local, x_local):
            # params_local: (1, ...) this stage's params; x_local: full batch
            params_local = jax.tree.map(lambda a: a[0], params_local)
            stage = lax.axis_index(axis)
            mb = x_local.reshape((n_microbatches, -1) + x_local.shape[1:])
            n_ticks = n_microbatches + n_stages - 1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

            def tick(carry, t):
                inflight, out = carry
                # stage 0 injects microbatch t (if any); others take the ring
                take = jnp.clip(t, 0, n_microbatches - 1)
                injected = mb[take]
                x_in = jnp.where(stage == 0, injected, inflight)
                y = fn(params_local, x_in)
                # last stage writes its result for microbatch (t - n_stages + 1)
                widx = t - (n_stages - 1)
                ok = (widx >= 0) & (stage == n_stages - 1)
                updated = lax.dynamic_update_index_in_dim(
                    out, y, jnp.clip(widx, 0, n_microbatches - 1), 0
                )
                out = jnp.where(ok, updated, out)
                nxt = lax.ppermute(y, axis, perm)
                return (nxt, out), None

            # carries become pod-varying inside the loop; mark them as such
            zero = pcast(jnp.zeros_like(mb[0]), (axis,), to="varying")
            out0 = pcast(jnp.zeros_like(mb), (axis,), to="varying")
            (_, out), _ = lax.scan(
                tick, (zero, out0), jnp.arange(n_ticks)
            )
            # every stage holds an `out` buffer; only the last stage's is
            # real — broadcast it by masking + psum (a one-source all-gather)
            if n_stages > 1:
                mask = (stage == n_stages - 1).astype(out.dtype)
                out = lax.psum(out * mask, axis)
            return out.reshape((-1,) + out.shape[2:])

        return shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
        )(stage_params, x)

    return wrapped
