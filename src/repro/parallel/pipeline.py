"""GPipe-style pipeline parallelism over a 'pipe' mesh axis.

Not enabled on the 512-chip production mesh (scan-over-layers + FSDP + TP
covers it; DESIGN.md §6), but provided — and tested — for fleets where a
third axis is worth it (e.g. (pipe=8, data=16, model=16) at 2048 chips,
where FSDP gathers would otherwise cross slow edges).

Implementation: shard_map over the pipe axis; each rank owns a contiguous
stage (a stack of layers it scans locally). The classic skew-and-rotate
schedule runs n_micro + n_stages - 1 ticks; activations hop stages via
collective_permute. Bubble fraction = (S-1)/(S-1+M).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,  # (stage_params, x) -> x, applied by every rank
    stage_params,  # pytree stacked on a leading 'stage' axis
    x: jnp.ndarray,  # (n_micro, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "pipe",
):
    """Run x through n_stages sequential stages, pipelined over ``axis``."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]

    def body(params_local, x_local):
        # params_local: this rank's stage params (leading axis of size 1)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        rank = jax.lax.axis_index(axis)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        state = jnp.zeros_like(x_local[0])
        outputs = jnp.zeros_like(x_local)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (while t < n_micro)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            injected = jax.lax.dynamic_index_in_dim(x_local, mb_idx, 0,
                                                    keepdims=False)
            state = jnp.where(rank == 0, injected, state)
            state = stage_fn(params_local, state)
            # the last stage emits microbatch t - (n_stages - 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = jnp.logical_and(rank == n_stages - 1,
                                   t >= n_stages - 1)
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, state, out_idx, 0),
                lambda o: o,
                outputs)
            # rotate activations one stage forward
            state = jax.lax.ppermute(state, axis, fwd_perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(n_micro + n_stages - 1))
        return outputs

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(None),  # every rank sees all microbatches (input broadcast)
    )

    out = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=P(axis, None),
                        check_vma=False)(stage_params, x)
    # out is (pipe, n_micro/..., ...) — only the last stage's slice holds
    # real outputs; gather it
    return out.reshape((n_stages, n_micro) + x.shape[1:])[-1]
