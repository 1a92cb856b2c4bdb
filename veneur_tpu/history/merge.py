"""Windowed-merge programs for range queries over the history ring.

One device program answers a whole batch of range queries: for every
requested STEP (a [t0, t1] slice of the lookback) the host selects the
minimal cover set of ring columns (writer.py plan_range) and ships a
{0,1} selection mask per step; the device folds the selected columns
per kind —

    counters / counts / sums   compensated two-float fold, ascending
                               column order (deterministic)
    gauges / status            last-writer-wins via a recency-rank
                               argmax over finite selected columns
    sets                       masked 6-bit register max (the Pallas
                               kernel in ops/pallas_history.py on a
                               TPU backend, the XLA fori chain
                               otherwise — bit-identical packed words)
    histos                     selected centroids re-compressed through
                               the ring's own k-cell compression, then
                               the shared quantile kernel

— and ships one packed f32 buffer back, exactly the flush program's
wire discipline (step.py _pack_outputs / unpack_flush). The combined
entry point `query_combined` evaluates an instant-query batch and a
range batch in ONE launch, which is what lets POST /query coalesce
both shapes into a single device program.

Byte-exactness contract: a range answer must equal re-merging the
archived flush frames. That holds by construction because the replay
oracle (tests/test_history.py, benchmarks config14) feeds the archived
frames through the SAME write/roll programs into a fresh ring and asks
the SAME merge program — every float op runs in the same order on the
same bits.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from veneur_tpu.aggregation.step import _pack_outputs
from veneur_tpu.history.device import HistoryState
from veneur_tpu.history.spec import HistorySpec
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import tdigest as td
from veneur_tpu.utils.numerics import twofloat_merge

# A range batch pads its step count to a power of two (min 4, cap 32)
# so arbitrary dashboards hit a handful of compiled variants — the same
# bucketing idea as pack_query_inputs' n_q padding.
MAX_STEPS = 32


def _merge_windows_xla(rows, sel, *, precision: int):
    """XLA fallback for the masked window merge: fori over columns,
    dense u8 register max under the step mask. rows i32[N, W, nw],
    sel f32[S, W] -> i32[N, S, nw] packed."""
    n, w, _nw = rows.shape
    s = sel.shape[0]
    r = hll_ops.num_registers(precision)

    def body(i, acc):
        words = jax.lax.dynamic_index_in_dim(rows, i, axis=1,
                                             keepdims=False)
        regs = hll_ops.unpack_registers(
            words, precision=precision).astype(jnp.int32)
        m = jax.lax.dynamic_index_in_dim(sel, i, axis=1, keepdims=False)
        cand = jnp.maximum(acc, regs[:, None, :])
        return jnp.where((m > 0.0)[None, :, None], cand, acc)

    acc = jax.lax.fori_loop(0, w, body,
                            jnp.zeros((n, s, r), jnp.int32))
    return hll_ops.pack_registers(acc.astype(jnp.uint8),
                                  precision=precision)


def merge_windows(rows, sel, *, precision: int):
    """Masked window merge: the Pallas kernel on a TPU backend, the XLA
    chain otherwise. Both return identical packed words (integer max
    commutes with the 6-bit packing), asserted in tests via interpret
    mode."""
    from veneur_tpu.ops import pallas_history
    if pallas_history.enabled():
        return pallas_history.merge_windows_packed(rows, sel,
                                                   precision=precision)
    return _merge_windows_xla(rows, sel, precision=precision)


def _fold_pair(hi_rows, lo_rows, sel):
    """Masked compensated fold of two-float pairs over the column axis:
    hi/lo f32[N, W], sel f32[S, W] -> (hi, lo) f32[N, S]. Ascending
    column order, fixed at trace time — the deterministic 'XLA chain'."""
    n = hi_rows.shape[0]
    s, w = sel.shape

    def body(i, carry):
        hi, lo = carry
        m = jax.lax.dynamic_index_in_dim(sel, i, axis=1, keepdims=False)
        xh = jax.lax.dynamic_index_in_dim(hi_rows, i, axis=1,
                                          keepdims=False)
        xl = jax.lax.dynamic_index_in_dim(lo_rows, i, axis=1,
                                          keepdims=False)
        return twofloat_merge(hi, lo, xh[:, None] * m[None, :],
                              xl[:, None] * m[None, :])

    z = jnp.zeros((n, s), jnp.float32)
    return jax.lax.fori_loop(0, w, body, (z, z))


def _lww(rows, sel, rank):
    """Last-writer-wins over selected finite columns: rows f32[N, W],
    sel f32[S, W], rank f32[W] (larger = newer) -> f32[N, S]; NaN when
    no selected column holds a value."""
    fin = jnp.isfinite(rows)                                  # [N, W]
    eff = jnp.where(fin[:, None, :] & (sel[None, :, :] > 0.0),
                    rank[None, None, :], -jnp.inf)            # [N, S, W]
    i = jnp.argmax(eff, axis=2)                               # [N, S]
    v = jnp.take_along_axis(
        jnp.broadcast_to(rows[:, None, :], eff.shape), i[..., None],
        axis=2)[..., 0]
    return jnp.where(jnp.max(eff, axis=2) == -jnp.inf,
                     jnp.float32(jnp.nan), v)


def range_merge_core(hist: HistoryState, qs, cidx, gidx, stidx, setidx,
                     hidx, sel, rank, *, hspec: HistorySpec):
    take = lambda a, i: jnp.take(a, i, axis=0, mode="clip")  # noqa: E731
    s = sel.shape[0]

    chi, clo = _fold_pair(take(hist.counter_hi, cidx),
                          take(hist.counter_lo, cidx), sel)
    gauge = _lww(take(hist.gauge, gidx), sel, rank)
    status = _lww(take(hist.status, stidx), sel, rank)

    merged = merge_windows(take(hist.hll, setidx), sel,
                           precision=hspec.hll_precision)
    est = hll_ops.estimate_packed_rows(merged,
                                       precision=hspec.hll_precision)

    mean = take(hist.h_mean, hidx)          # [bh, W, C]
    weight = take(hist.h_weight, hidx)
    hmin = take(hist.h_min, hidx)           # [bh, W]
    hmax = take(hist.h_max, hidx)
    bh = mean.shape[0]
    w = mean.shape[1]
    c = mean.shape[2]
    hq_steps, mn_steps, mx_steps = [], [], []
    for i in range(s):                       # static step count
        m = sel[i]                           # [W]
        wm = weight * m[None, :, None]
        cm, cw = td.compress_rows(
            mean.reshape(bh, w * c), wm.reshape(bh, w * c),
            compression=hspec.compression, cells_per_k=hspec.cells_per_k,
            out_c=hspec.centroids, exact_extremes=hspec.exact_extremes)
        mn = jnp.min(jnp.where(m[None, :] > 0, hmin, jnp.inf), axis=1)
        mx = jnp.max(jnp.where(m[None, :] > 0, hmax, -jnp.inf), axis=1)
        table = td.TDigestTable(
            mean=cm, weight=cw, min=mn, max=mx,
            count_hi=jnp.zeros((bh,), jnp.float32),
            count_lo=jnp.zeros((bh,), jnp.float32),
            sum_hi=jnp.zeros((bh,), jnp.float32),
            sum_lo=jnp.zeros((bh,), jnp.float32),
            recip_hi=jnp.zeros((bh,), jnp.float32),
            recip_lo=jnp.zeros((bh,), jnp.float32))
        hq_steps.append(td.quantiles(table, qs))
        mn_steps.append(mn)
        mx_steps.append(mx)
    hct_hi, hct_lo = _fold_pair(take(hist.h_count_hi, hidx),
                                take(hist.h_count_lo, hidx), sel)
    hs_hi, hs_lo = _fold_pair(take(hist.h_sum_hi, hidx),
                              take(hist.h_sum_lo, hidx), sel)
    return {
        "r_counter_hi": chi, "r_counter_lo": clo,
        "r_gauge": gauge, "r_status": status,
        "r_set_estimate": est,
        "r_histo_quantiles": jnp.stack(hq_steps, axis=1),
        "r_histo_min": jnp.stack(mn_steps, axis=1),
        "r_histo_max": jnp.stack(mx_steps, axis=1),
        "r_histo_count_hi": hct_hi, "r_histo_count_lo": hct_lo,
        "r_histo_sum_hi": hs_hi, "r_histo_sum_lo": hs_lo,
    }


def _range_in_packed_core(hist: HistoryState, hflat, *,
                          hspec: HistorySpec, n_q: int, n_steps: int,
                          buckets: tuple):
    """Packed-wire wrapper: hflat is ONE i32 buffer of
    [qs-bits | 5 row buckets | sel-bits | rank-bits] (pack_range_inputs
    builds it), the D2H side is one packed f32 buffer — the flush
    program's one-transfer-each-way discipline."""
    w = hspec.total_cols
    qs = jax.lax.bitcast_convert_type(hflat[:n_q], jnp.float32)
    idx, off = [], n_q
    for n in buckets:
        idx.append(hflat[off:off + n])
        off += n
    sel = jax.lax.bitcast_convert_type(
        hflat[off:off + n_steps * w], jnp.float32).reshape(n_steps, w)
    off += n_steps * w
    rank = jax.lax.bitcast_convert_type(hflat[off:off + w], jnp.float32)
    out = range_merge_core(hist, qs, *idx, sel, rank, hspec=hspec)
    return _pack_outputs(out)


range_in_packed = partial(
    jax.jit, static_argnames=("hspec", "n_q", "n_steps", "buckets"))(
        _range_in_packed_core)


def _query_combined_core(state, flat, hist, hflat, *, spec, n_q: int,
                         buckets: tuple, hspec: HistorySpec, hn_q: int,
                         hsteps: int, hbuckets: tuple):
    from veneur_tpu.aggregation.step import _flush_live_in_packed_core
    inst = _flush_live_in_packed_core(state, flat, spec=spec, n_q=n_q,
                                      buckets=buckets)
    rng = _range_in_packed_core(hist, hflat, hspec=hspec, n_q=hn_q,
                                n_steps=hsteps, buckets=hbuckets)
    return inst, rng


# One launch for a mixed instant+range batch: the query batcher
# dispatches this when a coalesced POST /query batch carries both
# shapes (query/engine.py _launch_on_pipeline).
query_combined = partial(
    jax.jit, static_argnames=("spec", "n_q", "buckets", "hspec",
                              "hn_q", "hsteps", "hbuckets"))(
        _query_combined_core)


def pad_steps(n: int) -> int:
    p = 4
    while p < n:
        p <<= 1
    return min(p, MAX_STEPS)


def pad_rows(n: int, cap: int) -> int:
    p = 4
    while p < n:
        p <<= 1
    return min(p, max(cap, 1))


def pack_range_inputs(hspec: HistorySpec, need, sel, rank, union_qs):
    """Host side: the range batch's gather plan -> (hflat, n_q, n_steps,
    buckets, qcol). `need` is (counter, gauge, status, set, histo) row
    lists in batch-match order; `sel` f32[S, W] selection masks from
    writer.plan_range; `rank` f32[W] recency ranks; `union_qs` the
    batch's union quantile set. Steps and quantiles pad to powers of
    two so variants stay bounded; pad steps carry all-zero masks and
    render as empty (host discards)."""
    import numpy as np
    w = hspec.total_cols
    qs = sorted(union_qs) or [0.5]
    n_q = 4
    while n_q < len(qs):
        n_q <<= 1
    qcol = {v: i for i, v in enumerate(qs)}
    qs_padded = np.asarray(qs + [0.5] * (n_q - len(qs)), np.float32)
    s_real = sel.shape[0]
    n_steps = pad_steps(s_real)
    if s_real > n_steps:
        raise ValueError("range step count exceeds MAX_STEPS")
    sel_p = np.zeros((n_steps, w), np.float32)
    sel_p[:s_real] = sel
    caps = tuple(hspec.rows_for(k) for k in range(5))
    buckets, idx_arrays = [], []
    for rows_list, cap in zip(need, caps):
        b = pad_rows(len(rows_list), cap)
        if len(rows_list) > b:
            raise ValueError("range gather exceeds history capacity")
        arr = np.zeros(b, np.int32)
        arr[:len(rows_list)] = rows_list
        buckets.append(b)
        idx_arrays.append(arr)
    flat = np.concatenate(
        [qs_padded.view(np.int32)]
        + [a.ravel() for a in idx_arrays]
        + [sel_p.ravel().view(np.int32),
           np.asarray(rank, np.float32).ravel().view(np.int32)])
    return flat, n_q, n_steps, tuple(buckets), qcol


def range_shapes(hspec: HistorySpec, buckets: tuple, n_steps: int,
                 n_q: int) -> dict:
    """unpack_flush shape table for the packed range output."""
    bc, bg, bst, bs, bh = buckets
    f32 = "float32"
    return {
        "r_counter_hi": ((bc, n_steps), f32),
        "r_counter_lo": ((bc, n_steps), f32),
        "r_gauge": ((bg, n_steps), f32),
        "r_status": ((bst, n_steps), f32),
        "r_set_estimate": ((bs, n_steps), f32),
        "r_histo_quantiles": ((bh, n_steps, n_q), f32),
        "r_histo_min": ((bh, n_steps), f32),
        "r_histo_max": ((bh, n_steps), f32),
        "r_histo_count_hi": ((bh, n_steps), f32),
        "r_histo_count_lo": ((bh, n_steps), f32),
        "r_histo_sum_hi": ((bh, n_steps), f32),
        "r_histo_sum_lo": ((bh, n_steps), f32),
    }
