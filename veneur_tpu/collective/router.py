"""Mesh programs for the collective global tier.

make_routed_ingest is the zero-serialization delivery path: a co-located
local tier's flush rows are staged host-side into per-(replica, source
shard, DEST shard) buckets, shipped to the mesh as one Batch with
leading [R, S_src, S_dest] dims, and routed to their owner shards by an
on-device `lax.all_to_all` over the shard axis INSIDE shard_map — after
which each owner tile applies its rows with the exact same ingest
scatter the local tiers use. No protobuf, no gRPC, no host round-trip:
the merge payload crosses the interconnect as device arrays.

make_merged_state runs the replica-axis sketch merge alone (no flush
math), producing one merged [S, ...] DeviceState for the raw checkpoint/
forward gather.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, PartitionSpec as P

from veneur_tpu.aggregation.state import DeviceState, TableSpec
from veneur_tpu.aggregation.step import ingest_core
from veneur_tpu.collective.ops import (
    REPLICA_AXIS, SHARD_AXIS, merge_replica_block, shard_map)


def shard_axis_is_physical(mesh: Mesh, n_shards: int) -> bool:
    """all_to_all routing needs the logical shard axis fully laid out on
    devices (one owner tile per shard); on collapsed fallback meshes the
    tier falls back to host-side owner bucketing, which is semantically
    identical (rows still land on their owner's scatter)."""
    return mesh.shape[SHARD_AXIS] == n_shards


def make_routed_ingest(mesh: Mesh, spec: TableSpec):
    """Jitted (state, batch) -> state. `batch` lanes carry leading
    [R, S_src, S_dest, B] dims: dim 1 is mesh placement (which shard
    column the rows start on), dim 2 the owner shard the stager routed
    each bucket to. Inside shard_map each tile all_to_alls dim 2 over
    the shard axis — turning it into a source index — then flattens the
    arriving buckets into one row batch for the owner's ingest scatter.

    Requires shard_axis_is_physical(mesh, n_shards) (tile dim 1 must be
    size 1 so dim 2 lines up with the physical axis)."""
    core = partial(ingest_core, spec=spec, allow_pallas=False)

    def block(state, batch):
        def route(x):
            # [r_l, 1, S_dest, B, ...] -> dest becomes source after the
            # exchange; fold sources into one flat row axis
            y = jax.lax.all_to_all(x, SHARD_AXIS, split_axis=2,
                                   concat_axis=2)
            return y.reshape(y.shape[:2] + (-1,) + y.shape[4:])

        routed = jax.tree.map(route, batch)
        return jax.vmap(jax.vmap(core))(state, routed)

    fn = shard_map(
        block, mesh=mesh,
        in_specs=(P(REPLICA_AXIS, SHARD_AXIS), P(REPLICA_AXIS, SHARD_AXIS)),
        out_specs=P(REPLICA_AXIS, SHARD_AXIS))
    return jax.jit(fn, donate_argnums=(0,))


def make_merged_state(mesh: Mesh, spec: TableSpec):
    """Jitted state[R,S,...] -> replica-merged DeviceState with leading
    [S] dim — the raw-gather twin of make_merged_flush (same
    merge_replica_block, no flush math)."""

    def block(state: DeviceState):
        return merge_replica_block(state, spec, REPLICA_AXIS)

    # replica-reduced outputs aren't replicated the way the checker wants
    fn = shard_map(block, mesh=mesh,
                   in_specs=(P(REPLICA_AXIS, SHARD_AXIS),),
                   out_specs=P(SHARD_AXIS), check_vma=False)
    return jax.jit(fn)
