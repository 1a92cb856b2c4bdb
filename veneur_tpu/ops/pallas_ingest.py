"""Fused Pallas ingest kernel: the whole scatter chain in one pass.

`aggregation/step.py ingest_core` is a chain of separate XLA scatters —
counter add, gauge/status last-write-wins, HLL register max, digest
cell insert — each of which re-streams its state operand through HBM.
This module fuses them into ONE `pl.pallas_call` over VMEM-tiled state
blocks: every state leaf is read into VMEM once, takes all of its
batch's updates in place, and is written back once.

Shape of the kernel:

- The host-side prologue sorts each kind's batch lane by (slot, batch
  index) — reusing `_histo_plan` verbatim for the digest lane so cell
  assignment math is shared, not duplicated — maps invalid slots to a
  2^30 sentinel, and computes per-grid-step window offsets with one
  searchsorted per leaf group. The offsets ride as a scalar-prefetch
  operand (`pltpu.PrefetchScalarGridSpec`), so block index maps and
  loop bounds know them before the body runs; the sorted streams ride
  in SMEM, the one memory a scalar can be read from by a dynamic index.
- A 1-D grid walks each group's blocks in slot order; a group with
  fewer blocks than the grid clamps its index map (`min(g, blocks-1)`),
  which under Pallas revisit semantics keeps its last block resident in
  VMEM with no extra HBM traffic. Out blocks are copy-initialized from
  the aliased inputs on first visit only (`@pl.when(g < blocks)` — the
  first visit of block b is exactly grid step b), then mutated by
  sequential read-modify-writes driven by
  `fori_loop(offs[k, g], offs[k, g + 1])`.
- Mosaic has no scalar store to VMEM ("Cannot store scalars to VMEM"),
  so every update is a read-modify-write of ONE (1, lanes) row under a
  lane mask: the row is a dynamic sublane offset, the lane position
  never is. Per-slot 1-D leaves are therefore viewed [K/128, 128] (a
  free reshape) and tile on their own; the two u8 stamp leaves ride as
  i32, since a single-row store of a packed dtype has no lowering
  either.
- Update order inside a window is ascending (slot, batch index), so per
  slot the adds/sets land in batch order. On CPU that is exactly the
  order XLA applies duplicate scatter updates, which makes the kernel
  BYTE-identical to the scatter chain on every state leaf
  (tests/test_pallas_ingest.py pins this in interpret mode). On a TPU,
  XLA's scatter-add orders duplicates its own way: the two paths are
  byte-identical wherever sums are exact, and an f32 sum of three or
  more addends may differ in its last bits (chip_smoke.py holds both).
- HLL registers update directly in the 6-bit packed words
  (ops/hll.py §packed): the field's one or two words are picked out of
  the row with a lane mask, the field is maxed with rho, and the words
  are put back under the same masks. Since 2^p % 16 == 0 a straddle
  never occurs at a row's final word, so the second word always exists.

Selection is `active()`: the `pallas_ingest_enabled` config key feeds
`set_enabled` at server construction; otherwise a TPU backend plus the
module constant `ENABLED` decide. On CPU the kernel runs in interpret
mode (traced JAX ops) — correct everywhere, used by the parity suite;
the production CPU path stays the XLA chain.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veneur_tpu.aggregation.state import DeviceState, TableSpec

_BIG = 1 << 30   # sentinel slot for invalid rows: beyond every window


_LANES = 128


def _lanes(cap: int) -> int:
    """Lane width L of a 1-D leaf's 2-D view [cap / L, L]: Mosaic has no
    scalar store to VMEM, so a per-slot leaf is updated one (1, L) row
    at a time under a lane mask, and wants the lane dim dense."""
    return _LANES if cap % _LANES == 0 else cap


def _tile_1d(cap: int, budget: int = 1 << 15) -> int:
    """Slots per block of a 1-D leaf: the whole leaf when it fits the
    budget (a block equal to the array is always legal), else the
    budget — a multiple of 8 * 128, so a block is whole (8, 128)
    tiles of the [cap / 128, 128] view."""
    if cap <= budget:
        return cap
    if cap % _LANES:
        raise ValueError(
            f"fused ingest: a table capacity above {budget} must be a "
            f"multiple of {_LANES}, got {cap}")
    return budget


def _row_tile(cap: int, budget: int) -> int:
    """Rows per block of a 2-D leaf: the whole table when it fits the
    budget, else the budget rounded down to the sublane tiling — Mosaic
    refuses a block whose tiled dims are neither aligned nor the full
    array dim. A ragged last block is legal, a whole one is preferred."""
    if cap <= budget:
        return cap
    top = max(8, budget // 8 * 8)
    for tile in range(top, 7, -8):      # prefer a tile that divides
        if cap % tile == 0:
            return tile
    return top


def _tiles(spec: TableSpec):
    """Per-group VMEM tile rows: counter, gauge, status, set, histo
    cells (h_w / h_wm) and histo scalars (the six per-row leaves, which
    tile on their own so that their blocks stay lane-dense). Budgeted
    so in+out blocks of every group, double-buffered, fit ~10MB at the
    default spec."""
    return (_tile_1d(spec.counter_capacity), _tile_1d(spec.gauge_capacity),
            _tile_1d(spec.status_capacity),
            _row_tile(spec.set_capacity, (1 << 18) // spec.hll_words),
            _row_tile(spec.histo_capacity, (1 << 17) // spec.stored_cells),
            _tile_1d(spec.histo_capacity))


def _layout(spec: TableSpec):
    tiles = _tiles(spec)
    caps = (spec.counter_capacity, spec.gauge_capacity,
            spec.status_capacity, spec.set_capacity, spec.histo_capacity,
            spec.histo_capacity)
    nblocks = tuple(-(-c // t) for c, t in zip(caps, tiles))
    return tiles, caps, nblocks, max(nblocks)


def _pad1(a):
    """A zero-length lane still needs a nonempty block; one sentinel
    row (slot == _BIG lands outside every window) keeps the BlockSpec
    legal without a second compiled variant."""
    if a.shape[0] > 0:
        return a
    return jnp.zeros((1,) + a.shape[1:], a.dtype)


def _stream(slot, cap, *vals, extra_valid=None):
    """Sort one lane by (slot, batch index); invalid rows — negative or
    past-capacity slots — keep their relative order at the tail under the
    _BIG sentinel, outside every window. (The XLA chain's mode="drop"
    scatters WRAP negative slots, NumPy-style; production never emits
    them — padding rows carry slot == capacity — so dropping here is the
    saner twin behavior, same call as hll.merge_rows_packed.)"""
    valid = (slot >= 0) & (slot < cap)
    if extra_valid is not None:
        valid = valid & extra_valid
    skey = jnp.where(valid, slot, _BIG)
    idx = jnp.arange(slot.shape[0], dtype=jnp.int32)
    order = jnp.lexsort((idx, skey))
    return (_pad1(skey[order].astype(jnp.int32)),
            tuple(_pad1(v[order]) for v in vals))


def _offsets(skeys, tiles, g_total):
    """i32[groups, G+1] window offsets: row k, step g covers sorted
    positions [offs[k, g], offs[k, g+1]) — the slots in
    [g*tile_k, (g+1)*tile_k). Steps past a group's last block get empty
    windows (every valid slot is below blocks_k * tile_k); sentinel rows
    sit past offs[k, G]."""
    rows = []
    for sk, t in zip(skeys, tiles):
        bounds = jnp.arange(g_total + 1, dtype=jnp.int32) * t
        rows.append(jnp.searchsorted(sk, bounds, side="left")
                    .astype(jnp.int32))
    return jnp.stack(rows)


def fused_ingest_core(state: DeviceState, batch, *, spec: TableSpec,
                      interpret: bool = False) -> DeviceState:
    """Drop-in replacement for ingest_core's scatter chain (everything
    except the optional histo_stat_* import lanes and the two-float
    fold, which stay in XLA around the kernel). Pure; safe under jit
    and donation — state leaves alias the kernel outputs (the two u8
    stamp leaves ride as i32 through the kernel: a single-row store of
    a packed dtype has no TPU lowering)."""
    from veneur_tpu.aggregation.step import _histo_plan

    tiles, caps, nblocks, g_total = _layout(spec)
    tc, tg, tst, ts, th, ths = tiles
    ncb, ngb, nstb, nsb, nhb, nhsb = nblocks
    lc, lg, lst, lh = (_lanes(caps[0]), _lanes(caps[1]), _lanes(caps[2]),
                       _lanes(caps[4]))
    w_words = spec.hll_words
    cells = spec.stored_cells

    # the XLA chain's scope names (step.ingest_core) on each kind's
    # stream preparation; the kernel itself is `fused_ingest`
    with jax.named_scope("ingest.counter"):
        c_sk, (c_inc,) = _stream(batch.counter_slot, spec.counter_capacity,
                                 batch.counter_inc)
    with jax.named_scope("ingest.gauge"):
        g_sk, (g_val,) = _stream(batch.gauge_slot, spec.gauge_capacity,
                                 batch.gauge_val)
    with jax.named_scope("ingest.status"):
        st_sk, (st_val,) = _stream(batch.status_slot, spec.status_capacity,
                                   batch.status_val)
    # the dense scatter drops out-of-range register indices too (2-D
    # scatter, mode="drop") — mirror that in the stream validity
    with jax.named_scope("ingest.set"):
        reg_ok = (batch.set_reg >= 0) & (batch.set_reg < spec.registers)
        s_sk, (s_reg, s_rho) = _stream(
            batch.set_slot, spec.set_capacity, batch.set_reg,
            batch.set_rho.astype(jnp.int32), extra_valid=reg_ok)
    with jax.named_scope("ingest.histo"):
        hs, h_cell, h_v, h_w, h_tadd = _histo_plan(
            state, batch.histo_slot, batch.histo_val, batch.histo_wt, spec)
    # _histo_plan already sorted by (slot, value) with invalid rows at
    # slot == histo_capacity; only the sentinel remap is needed, and the
    # kernel consumes the EXACT arrays the scatter chain would.
    h_sk = _pad1(jnp.where(hs < spec.histo_capacity, hs,
                           jnp.int32(_BIG)).astype(jnp.int32))
    h_cell, h_v, h_w, h_tadd = (_pad1(h_cell), _pad1(h_v),
                                _pad1(h_w), _pad1(h_tadd))
    h_wv = h_w * h_v
    h_rcp = jnp.where(h_w > 0, h_w / h_v, 0.0)

    offs = _offsets([c_sk, g_sk, st_sk, s_sk, h_sk, h_sk], tiles, g_total)

    def lane_iota(n):
        return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def locate(l, tile, lanes):
        """Block-local slot -> (row, lane) of the [tile / lanes, lanes]
        view; a single-row block needs no division."""
        if tile == lanes:
            return 0, l
        return l // lanes, l % lanes

    def kernel(offs_ref,
               counter_in, gauge_in, gstamp_in, status_in, ststamp_in,
               hll_in, hw_in, hwm_in, htn_in, hmin_in, hmax_in,
               hcnt_in, hsum_in, hrcp_in,
               c_slot_s, c_inc_s, g_slot_s, g_val_s, st_slot_s, st_val_s,
               s_slot_s, s_reg_s, s_rho_s,
               h_slot_s, h_cell_s, h_v_s, h_w_s, h_wv_s, h_rcp_s, h_tadd_s,
               counter_out, gauge_out, gstamp_out, status_out, ststamp_out,
               hll_out, hw_out, hwm_out, htn_out, hmin_out, hmax_out,
               hcnt_out, hsum_out, hrcp_out):
        g = pl.program_id(0)

        # copy-initialize out blocks from the aliased inputs on FIRST
        # visit only: the clamped index maps revisit each group's last
        # block, and re-copying would erase the resident RMW results
        for dst, src, nb in ((counter_out, counter_in, ncb),
                             (gauge_out, gauge_in, ngb),
                             (gstamp_out, gstamp_in, ngb),
                             (status_out, status_in, nstb),
                             (ststamp_out, ststamp_in, nstb),
                             (hll_out, hll_in, nsb),
                             (hw_out, hw_in, nhb),
                             (hwm_out, hwm_in, nhb),
                             (htn_out, htn_in, nhsb),
                             (hmin_out, hmin_in, nhsb),
                             (hmax_out, hmax_in, nhsb),
                             (hcnt_out, hcnt_in, nhsb),
                             (hsum_out, hsum_in, nhsb),
                             (hrcp_out, hrcp_in, nhsb)):
            @pl.when(g < nb)
            def _(dst=dst, src=src):
                dst[...] = src[...]

        # Every update is a read-modify-write of ONE (1, lanes) row under
        # a lane mask: the row index is dynamic (a sublane offset), the
        # lane position never is. Streams live in SMEM, where a scalar
        # can be read by a dynamic index.
        def update(ref, row, at, fn):
            cur = ref[pl.ds(row, 1), :]
            ref[pl.ds(row, 1), :] = jnp.where(at, fn(cur), cur)

        cbase = jnp.minimum(g, ncb - 1) * tc
        c_lane = lane_iota(lc)

        def c_body(i, _):
            row, lane = locate(c_slot_s[i] - cbase, tc, lc)
            inc = c_inc_s[i]
            update(counter_out, row, c_lane == lane, lambda x: x + inc)
            return 0

        jax.lax.fori_loop(offs_ref[0, g], offs_ref[0, g + 1], c_body, 0)

        gbase = jnp.minimum(g, ngb - 1) * tg
        g_lane = lane_iota(lg)

        def g_body(i, _):
            row, lane = locate(g_slot_s[i] - gbase, tg, lg)
            at = g_lane == lane
            val = g_val_s[i]
            update(gauge_out, row, at, lambda x: jnp.full_like(x, val))
            update(gstamp_out, row, at, lambda x: jnp.ones_like(x))
            return 0

        jax.lax.fori_loop(offs_ref[1, g], offs_ref[1, g + 1], g_body, 0)

        stbase = jnp.minimum(g, nstb - 1) * tst
        st_lane = lane_iota(lst)

        def st_body(i, _):
            row, lane = locate(st_slot_s[i] - stbase, tst, lst)
            at = st_lane == lane
            val = st_val_s[i]
            update(status_out, row, at, lambda x: jnp.full_like(x, val))
            update(ststamp_out, row, at, lambda x: jnp.ones_like(x))
            return 0

        jax.lax.fori_loop(offs_ref[2, g], offs_ref[2, g + 1], st_body, 0)

        sbase = jnp.minimum(g, nsb - 1) * ts
        w_lane = lane_iota(w_words)

        def s_body(i, _):
            row = s_slot_s[i] - sbase
            bit = 6 * s_reg_s[i]
            w0 = bit >> 5
            sh = bit & 31
            straddle = sh > 26
            nlo = jnp.where(straddle, 32 - sh, 6)
            nhi = 6 - nlo                     # 0 when the field fits
            mask_lo = (1 << nlo) - 1
            mask_hi = (1 << nhi) - 1
            w1 = jnp.where(straddle, w0 + 1, w0)
            words = hll_out[pl.ds(row, 1), :]             # [1, W]
            at0 = w_lane == w0
            at1 = w_lane == w1
            # the one or two words of the field, as [1, 1] (a masked
            # sum with a single live lane is exact)
            lo = jnp.sum(jnp.where(at0, words, 0), axis=1, keepdims=True)
            hi = jnp.sum(jnp.where(at1, words, 0), axis=1, keepdims=True)
            cur = ((lo >> sh) & mask_lo) | ((hi & mask_hi) << nlo)
            new = jnp.maximum(cur, s_rho_s[i])
            word0 = (lo & ~(mask_lo << sh)) | ((new & mask_lo) << sh)
            word1 = (hi & ~mask_hi) | (new >> nlo)
            words = jnp.where(at0, word0, words)
            words = jnp.where(at1 & straddle, word1, words)
            hll_out[pl.ds(row, 1), :] = words
            return 0

        jax.lax.fori_loop(offs_ref[3, g], offs_ref[3, g + 1], s_body, 0)

        hbase = jnp.minimum(g, nhb - 1) * th
        cell_lane = lane_iota(cells)

        def h_body(i, _):
            row = h_slot_s[i] - hbase
            at = cell_lane == h_cell_s[i]
            w = h_w_s[i]
            wv = h_wv_s[i]
            update(hw_out, row, at, lambda x: x + w)
            update(hwm_out, row, at, lambda x: x + wv)
            return 0

        jax.lax.fori_loop(offs_ref[4, g], offs_ref[4, g + 1], h_body, 0)

        hsbase = jnp.minimum(g, nhsb - 1) * ths
        hs_lane = lane_iota(lh)

        def hs_body(i, _):
            row, lane = locate(h_slot_s[i] - hsbase, ths, lh)
            at = hs_lane == lane
            v = h_v_s[i]
            w = h_w_s[i]
            wv = h_wv_s[i]
            rcp = h_rcp_s[i]
            tadd = h_tadd_s[i]
            lo = jnp.where(w > 0, v, jnp.inf)
            hi = jnp.where(w > 0, v, -jnp.inf)
            update(htn_out, row, at, lambda x: x + tadd)
            update(hmin_out, row, at, lambda x: jnp.minimum(x, lo))
            update(hmax_out, row, at, lambda x: jnp.maximum(x, hi))
            update(hcnt_out, row, at, lambda x: x + w)
            update(hsum_out, row, at, lambda x: x + wv)
            update(hrcp_out, row, at, lambda x: x + rcp)
            return 0

        jax.lax.fori_loop(offs_ref[5, g], offs_ref[5, g + 1], hs_body, 0)

    def view(a, lanes):
        return a.reshape(a.shape[0] // lanes, lanes)

    state_ins = (view(state.counter_acc, lc), view(state.gauge, lg),
                 view(state.gauge_stamp.astype(jnp.int32), lg),
                 view(state.status, lst),
                 view(state.status_stamp.astype(jnp.int32), lst),
                 state.hll, state.h_w, state.h_wm,
                 view(state.h_temp_n, lh), view(state.h_min, lh),
                 view(state.h_max, lh), view(state.h_count_acc, lh),
                 view(state.h_sum_acc, lh), view(state.h_recip_acc, lh))
    streams = (c_sk, c_inc, g_sk, g_val, st_sk, st_val,
               s_sk, s_reg, s_rho,
               h_sk, h_cell, h_v, h_w, h_wv, h_rcp, h_tadd)

    def rows(tile, lanes, nb):     # a 1-D leaf's [tile / lanes, lanes] block
        return pl.BlockSpec(
            (tile // lanes, lanes),
            lambda g, o, nb=nb: (jnp.minimum(g, nb - 1), 0))

    def table(tile, ncols, nb):
        return pl.BlockSpec((tile, ncols),
                            lambda g, o, nb=nb: (jnp.minimum(g, nb - 1), 0))

    state_specs = [
        rows(tc, lc, ncb), rows(tg, lg, ngb), rows(tg, lg, ngb),
        rows(tst, lst, nstb), rows(tst, lst, nstb),
        table(ts, w_words, nsb),
        table(th, cells, nhb), table(th, cells, nhb),
    ] + [rows(ths, lh, nhsb)] * 6
    stream_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(streams)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g_total,),
        in_specs=state_specs + stream_specs,
        out_specs=state_specs,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in state_ins],
        # operand 0 is the scalar-prefetch offsets; state input i is
        # operand i+1, aliased in place onto output i
        input_output_aliases={i + 1: i for i in range(len(state_ins))},
        interpret=interpret,
        name="fused_ingest",
    )(offs, *state_ins, *streams)
    flat = [o.reshape(-1) for o in outs]
    return state._replace(
        counter_acc=flat[0], gauge=flat[1],
        gauge_stamp=flat[2].astype(jnp.uint8),
        status=flat[3], status_stamp=flat[4].astype(jnp.uint8),
        hll=outs[5], h_w=outs[6], h_wm=outs[7], h_temp_n=flat[8],
        h_min=flat[9], h_max=flat[10],
        h_count_acc=flat[11], h_sum_acc=flat[12], h_recip_acc=flat[13])


# -- selection ---------------------------------------------------------------

# Module switch, see `active`. True: the kernel compiles for the v5e at
# the default widths (tests/test_tpu_compile.py) and agrees with the XLA
# chain on the chip (chip_smoke.py, kernels phase).
ENABLED = True

_OVERRIDE = None


def set_enabled(value) -> None:
    """Config-level override wired from `pallas_ingest_enabled` at server
    construction: False forces the XLA chain, True forces the kernel
    (interpret mode on CPU — the parity suite's switch), None restores
    the backend rule."""
    global _OVERRIDE
    _OVERRIDE = value


def interpret_mode() -> bool:
    """Run the kernel as traced JAX ops (bit-identical semantics, no
    Mosaic) — the portable mode tier-1 parity uses on CPU."""
    return jax.default_backend() == "cpu"


def active() -> bool:
    """Should ingest_core take the fused path right now? The config
    override first; otherwise the backend alone decides: on TPU the
    kernel runs (a failure to compile or run raises — no fallback), on
    CPU the XLA chain runs."""
    if _OVERRIDE is not None:
        return bool(_OVERRIDE)
    return ENABLED and jax.default_backend() == "tpu"
