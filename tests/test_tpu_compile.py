"""Compile the served path's device programs for a described TPU v5e.

The sandbox has no chip, but libtpu's compiler is installed and compiles
for a topology that is described rather than attached, so what the
chip's compiler would refuse — a program that does not fit HBM, a Pallas
block off the (8, 128) tiling, an op Mosaic cannot lower — fails here,
at the shipped default widths (config.py tpu_* defaults), before any
chip time is spent. Nothing runs: these tests say nothing about results
or speed (chip_smoke.py does, on the chip).

This is the only file that describes the chip. The topology is described
inside a module-scoped fixture (one process may load libtpu; under xdist
only the worker given this file does), and every sharding, mesh and
shape is built in a fixture or a test, never at import.
"""

import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from veneur_tpu.aggregation import step
from veneur_tpu.aggregation.host import BatchSpec
from veneur_tpu.aggregation.state import TableSpec, empty_state
from veneur_tpu.config import Config
from veneur_tpu.server.server import spec_from_config

GIB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next run warns and
    recompiles) — keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def default_spec() -> TableSpec:
    return spec_from_config(Config())


def _packed_sizes(spec):
    """Packed lane sizes of the shipped batch config: the compile key of
    the packed ingest program, derived as the aggregators derive it
    (sharded_aggregator.py; NativeAggregator._pk_sizes is the same
    tuple)."""
    from veneur_tpu.aggregation.host import Batcher
    cfg = Config()
    bspec = BatchSpec(counter=cfg.tpu_batch_counter,
                      gauge=cfg.tpu_batch_gauge,
                      status=cfg.tpu_batch_status, set=cfg.tpu_batch_set,
                      histo=cfg.tpu_batch_histo)
    return step.batch_sizes(Batcher(spec, bspec).force_emit())


@pytest.fixture(scope="module")
def default_sizes(default_spec):
    return _packed_sizes(default_spec)


# The digest table's height in the one-chip cases: the shipped default,
# and tpu_histo_capacity 131072, the 100,000-timer agent's (BASELINE
# configuration 2; perfbench/configs/agent-timers-1chip.json). Every
# bound below scales with it.
HISTO_ROWS = (16384, 131072)


@pytest.fixture(scope="module", params=HISTO_ROWS)
def served(request, default_spec):
    """(spec, packed sizes, height over the shipped height) of the
    one-chip served programs at one digest-table height."""
    spec = dataclasses.replace(default_spec, histo_capacity=request.param)
    return (spec, _packed_sizes(spec),
            request.param // default_spec.histo_capacity)


def _state_shapes(spec, sharding, lead=()):
    shapes = jax.eval_shape(partial(empty_state, spec))
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(lead + a.shape, a.dtype,
                                       sharding=sharding), shapes)


def _assert_digest_tables_in_rows(compiled, spec) -> int:
    """Wherever a digest table is a parameter or a result of the compiled
    program it lies in rows ('{1,0:T(8,128)}'), and the program holds no
    copy of a whole table. Returns how many parameters and results of a
    table's shape the entry computation has."""
    text = compiled.as_text()
    table = re.escape(f"f32[{spec.histo_capacity},{spec.stored_cells}]")
    entry = re.search(r"entry_computation_layout=\{(.*?)\}, \w+=", text)
    layouts = re.findall(table + r"(\{[^}]*\})", entry.group(1))
    assert all(ly.startswith("{1,0") for ly in layouts), layouts
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(table + r"\S* copy\(", ln)]
    assert not copies, copies
    return len(layouts)


def _flat(sizes, sharding, lead=()):
    words = step.packed_layout(sizes)[1]
    return jax.ShapeDtypeStruct(lead + (words,), jnp.int32,
                                sharding=sharding)


def test_default_spec_is_the_shipped_one(default_spec, default_sizes):
    """The widths these compiles run at are the ones the issue tables."""
    assert (default_spec.counter_capacity, default_spec.gauge_capacity,
            default_spec.status_capacity, default_spec.set_capacity,
            default_spec.histo_capacity) == (131072, 32768, 1024, 4096,
                                             16384)
    assert default_spec.hll_precision == 14
    assert default_spec.total_cells == 472
    assert default_spec.stored_cells == 512
    assert (default_sizes[0], default_sizes[2], default_sizes[4],
            default_sizes[6], default_sizes[9]) == (8192, 2048, 256, 4096,
                                                    8192)


def test_packed_ingest_program_compiles_under_1gib(one_chip, served):
    """ingest_step_packed's program — ingest, fold and the in-band
    compaction — on the XLA scatter chain: what serves wherever the
    fused kernel is not selected, and always under the sharded vmap.
    (The fused kernel has its own case below; the compaction it would
    share with this program is most of the compile time.) Under 1 GiB
    of temporaries at the shipped height, and in proportion above it."""
    spec, sizes, scale = served
    compiled = jax.jit(
        partial(step.packed_step_core, spec=spec, sizes=sizes),
        donate_argnums=(0,)).lower(
        _state_shapes(spec, one_chip), _flat(sizes, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < scale * GIB, mem
    assert "tpu_custom_call" not in compiled.as_text()


def test_packed_step_keeps_the_digest_tables_in_rows(one_chip, served,
                                                     monkeypatch):
    """ingest_step_packed as the chip runs it (the fused kernel
    selected): a digest row is stored stored_cells = 512 wide, so the
    device's default layout of both tables is rows and the program takes
    them, works on them and hands them back so. It holds no copy of a
    whole table, where it held six and ran four a step while the tables
    were 472 wide and lay column-major by default (3.08 of 9.24 ms a step
    at 131072 rows: PERF.md, PR 33), and its temporaries stay under a
    quarter of the chain's bound. Nothing is stated to the compiler; the
    program keeps the name the trace readers look for."""
    from veneur_tpu.ops import pallas_ingest
    monkeypatch.setattr(pallas_ingest, "active", lambda: True)
    monkeypatch.setattr(pallas_ingest, "interpret_mode", lambda: False)
    spec, sizes, scale = served
    compiled = step.ingest_step_packed.lower(
        _state_shapes(spec, one_chip), _flat(sizes, one_chip),
        spec=spec, sizes=sizes).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_packed_step_core,"), text[:80]
    assert "tpu_custom_call" in text
    # h_wm and h_w, in and out
    assert _assert_digest_tables_in_rows(compiled, spec) == 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < scale * GIB // 4, mem


def test_compress_rows_compiles_without_scatter_or_gather(one_chip, served):
    """compress_rows on one block of compact_core's loop, [R, 512] (a
    stored row: 472 cells and their pad) -> 280 as shipped: the chip's
    compiler fuses its compare and select into its reduces, so the
    program holds a few [R, M] arrays and nothing the size of the
    [R, M, out_c] compare, and no scatter or gather (the scatter form
    took 0.57 s a call on the v5e; PERF.md PR 29). The block does not
    grow with the table, so both heights compile the same program."""
    from veneur_tpu.ops import tdigest as td
    spec = served[0]
    r, m_len = step.COMPACT_ROW_BLOCK, spec.stored_cells
    rows = jax.ShapeDtypeStruct((r, m_len), jnp.float32, sharding=one_chip)
    compiled = jax.jit(partial(
        td.compress_rows, compression=spec.compression,
        cells_per_k=spec.cells_per_k, out_c=spec.centroids,
        exact_extremes=spec.exact_extremes)).lower(rows, rows).compile()
    text = compiled.as_text()
    assert " scatter(" not in text and " gather(" not in text
    assert " sort(" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * r * m_len * 4, mem


def test_compaction_compiles_with_whole_row_copies_only(one_chip, served):
    """compact_core over the digest table at both heights: the only
    gathers and scatters of table rows are the loop's copies of whole
    rows (slice width = stored_cells; an element-wise gather inside a row
    is what PR 29 took out), told that their ids are sorted and unique.
    The tables lie in rows by default (stored_cells is a multiple of 128
    lanes), so the row copies work on them in place: no copy of a whole
    table, where two went in and two came out while a row was 472 wide,
    and the temporaries are a few blocks of rows, under one table's
    worth at the shipped height."""
    spec, _sizes, scale = served
    compiled = jax.jit(partial(step.compact_core, spec=spec),
                       donate_argnums=(0,)).lower(
        _state_shapes(spec, one_chip)).compile()
    text = compiled.as_text()
    n, m_len = spec.histo_capacity, spec.stored_cells
    table = re.escape(f"f32[{n},{m_len}]")
    block = re.escape(f"f32[{step.COMPACT_ROW_BLOCK},{m_len}]")
    gathers = [ln for ln in text.splitlines() if " gather(" in ln]
    scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
    row_gathers = [ln for ln in gathers if re.search(
        block + r"\S* gather\(", ln)]
    assert len(row_gathers) == 2 and len(scatters) == 2, (gathers, scatters)
    for ln in row_gathers:
        assert f"slice_sizes={{1,{m_len}}}" in ln, ln
        assert "indices_are_sorted=true" in ln, ln
    for ln in scatters:
        assert re.search(table + r"\S* scatter\(", ln), ln
        assert "update_window_dims={1}" in ln, ln
        assert "indices_are_sorted=true" in ln, ln
        assert "unique_indices=true" in ln, ln
    # any other gather moves no table cell: none yields a float array
    for ln in gathers:
        assert ln in row_gathers or " f32[" not in ln.split(" gather(")[0], ln
    assert " sort(" in text
    assert _assert_digest_tables_in_rows(compiled, spec) == 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * n * m_len * 4 // scale, mem


def test_live_flush_program_compiles(one_chip, served, monkeypatch):
    """flush_live_in_packed at full-capacity live buckets and the served
    percentiles (0.5/0.75/0.99), on the XLA quantile path (the Pallas
    quantile kernel has its own case, and rides the sharded flush
    below). At 131072 rows the digest bucket is one whole
    FLUSH_BLOCK_ROWS block, the largest the flush ever runs."""
    from veneur_tpu.ops import pallas_digest
    monkeypatch.setattr(pallas_digest, "enabled", lambda: False)
    spec, _sizes, scale = served
    buckets = (spec.counter_capacity, spec.gauge_capacity,
               spec.status_capacity, spec.set_capacity, spec.histo_capacity)
    n_q = 3
    flat = jax.ShapeDtypeStruct((n_q + sum(buckets),), jnp.int32,
                                sharding=one_chip)
    compiled = jax.jit(partial(
        step._flush_live_in_packed_core, spec=spec, n_q=n_q,
        buckets=buckets)).lower(
            _state_shapes(spec, one_chip), flat).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < scale * GIB, mem


def test_live_flush_program_takes_the_digest_tables_in_rows(one_chip,
                                                            served):
    """flush.gather wants rows too, and gets the tables so: no copy of a
    table on the way in (two, a pass over each table, while a row was 472
    wide). The digest bucket is half the table, so that nothing else in
    the program has a table's shape."""
    spec = served[0]
    buckets = (spec.counter_capacity, spec.gauge_capacity,
               spec.status_capacity, spec.set_capacity,
               spec.histo_capacity // 2)
    n_q = 3
    flat = jax.ShapeDtypeStruct((n_q + sum(buckets),), jnp.int32,
                                sharding=one_chip)
    compiled = step.flush_live_in_packed.lower(
        _state_shapes(spec, one_chip), flat, spec=spec, n_q=n_q,
        buckets=buckets).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit__flush_live_in_packed_core,")
    assert _assert_digest_tables_in_rows(compiled, spec) == 2


def test_digest_quantile_kernel_compiles(one_chip):
    from veneur_tpu.ops import pallas_digest
    assert pallas_digest.ENABLED
    r, c, q = 16384, 472, 3

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(pallas_digest.quantiles_rows).lower(
        s(r, c), s(r, c), s(r), s(r), s(q)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_history_merge_kernel_compiles(one_chip):
    """The range-query HLL merge at p=14 over a whole default ring (360
    columns), 64 set rows, 8 steps."""
    from veneur_tpu.history.spec import HistorySpec
    from veneur_tpu.ops import hll, pallas_history
    assert pallas_history.ENABLED
    hs = HistorySpec()
    n, w, steps, p = 64, hs.total_cols, 8, hs.hll_precision
    rows = jax.ShapeDtypeStruct((n, w, hll.packed_words(p)), jnp.int32,
                                sharding=one_chip)
    sel = jax.ShapeDtypeStruct((steps, w), jnp.float32, sharding=one_chip)
    compiled = jax.jit(partial(pallas_history.merge_windows_packed,
                               precision=p)).lower(rows, sel).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_ingest_kernel_compiles(one_chip, served):
    """The kernel alone (plus the fold), every state leaf aliased in
    place: no temporaries beyond the sorted streams, as long as the two
    digest tables fit the chip's 128 MiB of VMEM together (2 x 34 MB as
    shipped). The tables lie in rows by default (a stored row is 512
    wide) and the kernel reads rows, so nothing is copied into a layout
    of the kernel's own: at 472 wide each table went into rows and back,
    through HBM temporaries of rows x 512 lanes above that size (2 x 268
    MB at 131072 rows)."""
    from veneur_tpu.ops import pallas_ingest
    assert pallas_ingest.ENABLED
    spec, sizes, scale = served

    def prog(state, flat):
        return step._fold_core(pallas_ingest.fused_ingest_core(
            state, step.unpack_batch(flat[1:], sizes),
            spec=spec, interpret=False))

    compiled = jax.jit(prog, donate_argnums=(0,)).lower(
        _state_shapes(spec, one_chip), _flat(sizes, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert _assert_digest_tables_in_rows(compiled, spec) == 4
    assert mem.temp_size_in_bytes < scale * 64 << 20, mem


@pytest.fixture(scope="module")
def mesh4(topo, default_spec):
    """The four-chip host's default: one shard per chip, per-shard
    capacities = default / 4."""
    import numpy as np

    from veneur_tpu.parallel.sharded import REPLICA_AXIS, SHARD_AXIS
    from veneur_tpu.server.sharded_aggregator import per_shard_spec
    n = 4
    mesh = Mesh(np.asarray(topo.devices).reshape(1, n),
                (REPLICA_AXIS, SHARD_AXIS))
    return (mesh, per_shard_spec(default_spec, n),
            NamedSharding(mesh, P(REPLICA_AXIS, SHARD_AXIS)))


def test_sharded_ingest_compiles_on_4_device_mesh(mesh4, default_sizes):
    """The vmapped XLA chain under shard_map, one tile per device."""
    from veneur_tpu.parallel.sharded import make_sharded_ingest_packed
    mesh, pspec, sh = mesh4
    n = mesh.devices.size
    fn = make_sharded_ingest_packed(mesh, pspec, default_sizes)
    compiled = fn.lower(_state_shapes(pspec, sh, lead=(1, n)),
                        _flat(default_sizes, sh, lead=(1, n))).compile()
    mem = compiled.memory_analysis()
    # per-device bytes: a quarter of the table plus O(batch) temporaries
    assert mem.argument_size_in_bytes < 64 << 20, mem
    assert mem.temp_size_in_bytes < GIB, mem
    # each tile's scatters stay on its own device
    assert "all-" not in compiled.as_text()


def test_sharded_flush_compiles_on_4_device_mesh(mesh4, monkeypatch):
    """The merged flush of the sharded backend with the Pallas quantile
    kernel under vmap inside shard_map — what four chips select."""
    from veneur_tpu.ops import pallas_digest
    from veneur_tpu.parallel.sharded import make_merged_flush
    monkeypatch.setattr(pallas_digest, "enabled", lambda: True)
    mesh, pspec, sh = mesh4
    n = mesh.devices.size
    qs = jax.ShapeDtypeStruct((3,), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    compiled = make_merged_flush(mesh, pspec).lower(
        _state_shapes(pspec, sh, lead=(1, n)), qs).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < GIB, mem
    assert "tpu_custom_call" in compiled.as_text()
