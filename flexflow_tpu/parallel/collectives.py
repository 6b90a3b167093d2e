"""Hand-scheduled collectives over the device mesh.

TPU-native equivalent of the reference's explicit communication layer
(reference: NCCL allreduce in src/runtime/optimizer_kernel.cu:88,196 and
the Legion region-movement realized by src/parallel_ops). The standard
path lets GSPMD emit collectives from shardings; this module provides
shard_map-scheduled versions for the cases where hand placement matters
(ring attention, expert all-to-all, and the simulator's comm-cost
validation).

All functions take a ``Mesh`` and axis name and are jit-compatible.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def ring_all_reduce(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """All-reduce over ``axis`` scheduled as reduce-scatter + all-gather
    rides of the ICI ring via collective-permute — the NCCL-ring algorithm
    (reference: optimizer_kernel.cu ncclAllReduce) expressed in XLA.

    Provided for schedule experimentation; ``jax.lax.psum`` (which XLA
    lowers to the same ring on TPU) is the production path.
    """
    n = mesh.shape[axis]
    if n == 1:
        return x

    def body(xs):
        # reduce-scatter: n-1 ring steps; in step s device d sends chunk
        # (d - s) mod n and accumulates into the received chunk
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % n) for i in range(n)]
        acc = jnp.stack(jnp.split(xs, n, axis=0))  # (n, chunk, ...)

        def rs_step(s, acc):
            send_i = (idx - s) % n
            sent = jax.lax.ppermute(acc[send_i], axis, perm)
            recv_i = (idx - s - 1) % n
            return acc.at[recv_i].add(sent)

        acc = jax.lax.fori_loop(0, n - 1, rs_step, acc)
        # device d now owns the fully-reduced chunk (d + 1) mod n
        own = (idx + 1) % n
        full = jax.lax.all_gather(acc[own], axis, tiled=False)  # (n, chunk,…)
        # gathered slot d holds reduced chunk (d+1)%n; chunk c sits at
        # slot (c-1)%n
        full = jnp.take(full, (jnp.arange(n) - 1) % n, axis=0)
        return jnp.concatenate(list(full), axis=0)

    spec = P(axis)
    fn = shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
    # operate over leading dim: requires x leading dim divisible by n
    return fn(x)


def psum_all_reduce(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Production all-reduce: psum under shard_map (XLA picks the ring)."""
    fn = shard_map(
        lambda v: jax.lax.psum(v, axis),
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(),
    )
    return fn(x)


def expert_all_to_all(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """All-to-all for expert parallelism: redistribute (experts, capacity,
    d) so each device holds its experts' tokens (reference analog: the
    data movement of group_by/aggregate when experts are sharded —
    SURVEY.md §2.3 EP). x sharded on dim 1 (tokens), returns x sharded on
    dim 0 (experts)."""

    def body(xs):
        return jax.lax.all_to_all(xs, axis, split_axis=0, concat_axis=1, tiled=True)

    fn = shard_map(body, mesh=mesh, in_specs=P(None, axis), out_specs=P(axis, None))
    return fn(x)


def experts_to_tokens(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Inverse of :func:`expert_all_to_all`: x sharded on dim 0 (experts),
    returns x sharded on dim 1 (tokens) — the combine-side data movement of
    expert parallelism (reference analog: aggregate.cu gathering expert
    outputs back to the token-owning devices)."""

    def body(xs):
        return jax.lax.all_to_all(xs, axis, split_axis=1, concat_axis=0, tiled=True)

    fn = shard_map(body, mesh=mesh, in_specs=P(axis, None), out_specs=P(None, axis))
    return fn(x)
