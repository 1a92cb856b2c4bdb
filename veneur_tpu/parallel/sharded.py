"""Device-mesh sharding of the key table and the collective global merge.

The reference's two distribution axes (SURVEY §2.4) map onto a 2-D
`jax.sharding.Mesh`:

- **"shard"** — key-space parallelism: `Digest % numWorkers` routing
  (reference server.go:973,984) becomes a leading shard axis on every state
  array, partitioned across devices. Each key lives on exactly one device
  (host.py assigns slot = shard * per_shard + local), so the ingest scatter
  never crosses devices — the per-worker-private-maps property of the
  reference (worker.go:60-84), expressed as sharding.
- **"replica"** — the local→global aggregation tier: each replica group
  accumulates its own sample stream (one "local veneur instance" worth of
  state); the flush-time merge the reference does over gRPC
  (importsrv/server.go:102 → samplers Merge methods) becomes on-device
  collectives over ICI: `psum` for counters/histogram scalars, register-max
  for HLL, all-gather + re-compress for t-digest centroids, and a
  stamp-argmax for last-write-wins gauges.

All state arrays carry leading dims [R, S] (replica, shard) and are laid out
with `NamedSharding(mesh, P("replica", "shard"))`; compute enters via
`jax.shard_map`, inside which each device sees its [r_local, s_local] block
and runs the same per-table ingest core under double vmap.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.aggregation.state import DeviceState, TableSpec, empty_state
from veneur_tpu.aggregation.step import Batch, flush_core, ingest_core
# the replica-tier merge collectives live in collective/ops.py (reusable
# over any named axis); this module keeps the mesh/state plumbing and the
# historical names
from veneur_tpu.collective.ops import (
    REPLICA_AXIS, SHARD_AXIS, merge_replica_block,
    shard_map as _shard_map)


def make_mesh(n_replicas: int, n_shards: int, devices=None) -> Mesh:
    """A (replica, shard) mesh over `n_replicas * n_shards` devices, or —
    with fewer physical devices — the largest (nr, ns) mesh where nr divides
    n_replicas and ns divides n_shards. shard_map blocks then hold multiple
    logical tiles per device (leading block dims > 1), which the vmapped
    cores handle transparently."""
    import numpy as np
    if devices is None:
        devices = jax.devices()
    need = n_replicas * n_shards
    if len(devices) >= need:
        return Mesh(np.asarray(devices[:need]).reshape(n_replicas, n_shards),
                    (REPLICA_AXIS, SHARD_AXIS))
    nr, ns = max(
        ((r, s) for r in range(1, n_replicas + 1) if n_replicas % r == 0
         for s in range(1, n_shards + 1) if n_shards % s == 0
         and r * s <= len(devices)),
        key=lambda p: p[0] * p[1])
    return Mesh(np.asarray(devices[:nr * ns]).reshape(nr, ns),
                (REPLICA_AXIS, SHARD_AXIS))


def state_sharding(mesh: Mesh):
    return NamedSharding(mesh, P(REPLICA_AXIS, SHARD_AXIS))


def sharded_empty_state(spec: TableSpec, n_replicas: int, n_shards: int,
                        mesh: Mesh) -> DeviceState:
    """DeviceState whose arrays have leading [R, S] dims, device-placed with
    (replica, shard) sharding. `spec` capacities are PER SHARD."""
    one = empty_state(spec)
    sh = state_sharding(mesh)

    def tile(x):
        tiled = jnp.broadcast_to(x, (n_replicas, n_shards) + x.shape)
        return jax.device_put(tiled, sh)

    return jax.tree.map(tile, one)


def stack_batches(batches, n_replicas: int, n_shards: int) -> Batch:
    """Stack a [R][S] nested list of per-shard Batches into one Batch with
    leading [R, S] dims (host-side numpy; feed to the sharded ingest).
    Optional lanes (None, e.g. histo_stat_* on pure-ingest batches) stay
    None — every tile must agree on which lanes are present."""
    import numpy as np
    cols = list(zip(*[list(zip(*[batches[r][s] for s in range(n_shards)]))
                      for r in range(n_replicas)]))

    def stack(col):
        flat = [x for row in col for x in row]
        if all(x is None for x in flat):
            return None
        if any(x is None for x in flat):
            raise ValueError(
                "stack_batches: every tile must agree on which optional "
                "Batch lanes are present (mixing Batcher batches with "
                "hand-built ones?)")
        return np.stack([np.stack(row) for row in col])

    return Batch(*[stack(col) for col in cols])


def make_sharded_ingest(mesh: Mesh, spec: TableSpec):
    """Jitted (state, batch) -> state over the mesh. Batch arrays must carry
    the same leading [R, S] dims as the state; each (replica, shard) tile's
    scatters stay on its own device — zero communication."""
    core = partial(ingest_core, spec=spec, allow_pallas=False)
    vv = jax.vmap(jax.vmap(core))

    def sharded_ingest(state, batch):
        return vv(state, batch)

    fn = _shard_map(
        sharded_ingest, mesh=mesh,
        in_specs=(P(REPLICA_AXIS, SHARD_AXIS), P(REPLICA_AXIS, SHARD_AXIS)),
        out_specs=P(REPLICA_AXIS, SHARD_AXIS))
    return jax.jit(fn, donate_argnums=(0,))


def make_sharded_ingest_packed(mesh: Mesh, spec: TableSpec, sizes: tuple):
    """Packed-transfer variant of make_sharded_ingest: (state, flat) ->
    (state, rows) where flat is i32[R, S, W] — each tile's batch as ONE
    bit-packed buffer (aggregation/step.py pack_batch), with the compact
    control word in-band — and rows is i32[R, S], the digest rows each
    tile's compaction compressed (0 on a step without one). Same
    single-executable / single-transfer rationale as the single-device
    ingest_step_packed, applied per mesh tile.

    The compact cond sits ABOVE the tile vmaps with a scalar predicate
    (every tile of a dispatch carries the same word): a vmapped cond
    would lower to a select that computes BOTH branches, running the
    sort-based recompression every step instead of every
    compact_every-th."""
    from veneur_tpu.aggregation.step import (
        compact_core, dirty_rows, ingest_core, unpack_batch)

    def tile_ingest(state, flat):
        # allow_pallas=False: the tile body runs under two vmaps, where
        # the fused kernel's scalar-prefetch grid does not apply
        with jax.named_scope("unpack"):
            batch = unpack_batch(flat[1:], sizes)
        return ingest_core(state, batch, spec=spec, allow_pallas=False)

    vv_ingest = jax.vmap(jax.vmap(tile_ingest))
    vv_compact = jax.named_scope("compact")(
        jax.vmap(jax.vmap(partial(compact_core, spec=spec))))

    # the inner function's name is the program's: `jit_sharded_packed_step`
    # on the profiler's XLA Modules line, apart from the flush's
    def sharded_packed_step(state, flat):
        st = vv_ingest(state, flat)
        do_compact = flat[0, 0, 0] != 0   # scalar: cond stays a branch
        with jax.named_scope("maybe_compact"):
            rows = jnp.where(do_compact, jax.vmap(jax.vmap(dirty_rows))(st),
                             0)
            return jax.lax.cond(do_compact, vv_compact, lambda s: s,
                                st), rows

    tiles = P(REPLICA_AXIS, SHARD_AXIS)
    fn = _shard_map(
        sharded_packed_step, mesh=mesh, in_specs=(tiles, tiles),
        out_specs=(tiles, tiles))
    return jax.jit(fn, donate_argnums=(0,))


def _merge_replica_block(state: DeviceState, spec: TableSpec):
    """Inside shard_map: merge a [r_local, s_local, ...] block over the full
    replica axis. The per-family sketch merges live in collective/ops.py
    (generalized over the axis name); this wrapper pins the replica axis."""
    return merge_replica_block(state, spec, REPLICA_AXIS)


def make_merged_flush(mesh: Mesh, spec: TableSpec):
    """Jitted (state[R,S,...], qs[Q]) -> flush dict with leading [S] dim:
    replica-merged, per-shard final aggregates. The replica merge is the
    reference's global-tier import (SURVEY §3.4) as one collective program;
    the flush math is flush_core per shard."""

    def sharded_merged_flush(state: DeviceState, qs):
        # _merge_replica_block already re-compresses digests to canonical
        # cells; no separate compact pass needed before the flush math.
        with jax.named_scope("flush.replica_merge"):
            merged = _merge_replica_block(state, spec)
        out = jax.vmap(lambda st: flush_core(st, qs, spec=spec))(merged)
        return out

    # replica-reduced outputs aren't replicated the way the checker wants
    fn = _shard_map(
        sharded_merged_flush, mesh=mesh,
        in_specs=(P(REPLICA_AXIS, SHARD_AXIS), P()),
        out_specs=P(SHARD_AXIS),
        check_vma=False)
    return jax.jit(fn)
