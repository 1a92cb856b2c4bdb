"""Pallas TPU kernel for t-digest quantiles: per-row bitonic sort +
prefix-sum + piecewise-linear interpolation fused in VMEM.

The XLA path (ops/tdigest.py quantiles) lowers to a generic variadic
sort, a gather, and several elementwise passes — each a round-trip
through HBM over the [rows, cells] arrays. Rows are independent and a
row (512 cells after padding at the production 472-column layout) fits
comfortably in VMEM, so the whole reduction is one kernel: load a tile
of rows, sort each row's
(mean, weight) pairs with a fixed bitonic network (static shapes — the
digest's cell count is compile-time), cumsum, and evaluate the midpoint
interpolation for every requested quantile without ever leaving VMEM.

The sort is the standard vectorized bitonic network, its
compare-exchange expressed with static circular shifts + iota masks
(no dynamic indexing — Pallas/TPU wants static addressing),
~log²(C)/2 vectorized passes over the tile.
Interpolation avoids gathers entirely: for each quantile, every
adjacent centroid interval computes its candidate value and a one-hot
interval mask selects the right one (VPU-friendly mask+reduce).

Used by ops/tdigest.quantiles when `enabled()`: a TPU backend and the
module constant. Parity with the XLA path is asserted bit-tolerantly in
tests/test_pallas_digest.py using interpret mode, which runs the same
kernel on CPU, and on the chip by chip_smoke.py.

What Mosaic accepts shaped this kernel (tests/test_tpu_compile.py
compiles it for the v5e at production widths): jnp.cumsum has no TPU
lowering (hence _prefix_sum_last), the textbook [..., C/2j, 2, j]
compare-exchange reshape is an interleaved vector reshape it rejects
(hence rot+mask), and a select or == whose OPERANDS are bool vectors
goes through an i8 round trip it cannot truncate back (hence the mask
algebra in _bitonic_sort_pairs).

Reference behavioral contract: merging_digest.go:302 Quantile (midpoint
interpolation between centroid masses, min/max endpoints).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# rows per grid step at ≤256 cells; quantiles_rows halves this beyond
# 256 padded cells so the [tile, c_pad] f32 working set (inputs + sort
# temporaries) stays ~constant (≈0.5MB/array) as rows widen
ROW_TILE = 256


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _bitonic_sort_pairs(key, val):
    """Sort (key, val) rows ascending by key along the last axis with a
    bitonic network. Static shapes only: last dim must be a power of two.
    key/val: f32[..., C].

    The compare-exchange is expressed with static circular shifts plus
    iota masks rather than the textbook reshape to [..., C/2j, 2, j]:
    Mosaic rejects those interleaved vector reshapes on real TPU
    (`tpu.reshape vector<256x128xf32> -> vector<256x64x2x1xf32>`), while
    concat-slices and elementwise selects lower cleanly. Each position i
    fetches its partner i^j via a shift of +-j (partner pairs never
    wrap: i|j < C), then keeps min or max per the block direction."""
    c = key.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)

    def rot(x, j):
        # circular left shift by j: position i sees x[(i+j) % C]
        return jnp.concatenate([x[..., j:], x[..., :j]], axis=-1)

    k = 2
    while k <= c:
        log2k = k.bit_length() - 1
        j = k // 2
        while j >= 1:
            log2j = j.bit_length() - 1
            is_lo = (pos & j) == 0                # partner is at i + j
            pk = jnp.where(is_lo, rot(key, j), rot(key, c - j))
            pv = jnp.where(is_lo, rot(val, j), rot(val, c - j))
            # ascending k-block (bit log2k clear) keeps the min at the
            # low partner (bit log2j clear): keep_min <=> the two bits
            # agree. Compared as i32 and combined with &,|,~ — Mosaic
            # has no select or == over i1 OPERANDS (it round-trips them
            # through i8 and cannot truncate back).
            keep_min = (((pos >> log2k) ^ (pos >> log2j)) & 1) == 0
            take = (keep_min & (pk < key)) | (~keep_min & (pk > key))
            key = jnp.where(take, pk, key)
            val = jnp.where(take, pv, val)
            j //= 2
        k *= 2
    return key, val


def _prefix_sum_last(x):
    """Inclusive prefix sum along the last axis via log-step shift-adds
    (Hillis-Steele): ceil(log2 C) static concat+slice passes instead of
    jnp.cumsum,
    whose primitive has no Mosaic TPU lowering (`Unimplemented
    primitive ... cumsum`). Shapes are static, so
    every shift is a compile-time slice the VPU vectorizes."""
    c = x.shape[-1]
    zeros = jnp.zeros_like(x)
    d = 1
    while d < c:
        shifted = jnp.concatenate(
            [zeros[..., :d], x[..., :c - d]], axis=-1)
        x = x + shifted
        d *= 2
    return x


def _quantile_kernel(qs_ref, m_ref, w_ref, mn_ref, mx_ref, out_ref,
                     *, n_q: int):
    m = m_ref[...]                                   # [T, C]
    w = w_ref[...]
    mn = mn_ref[...]                                 # [T, 1]
    mx = mx_ref[...]
    live = w > 0
    key = jnp.where(live, m, jnp.float32(jnp.inf))
    skey, sw = _bitonic_sort_pairs(key, jnp.where(live, w, 0.0))
    tot = jnp.sum(sw, axis=-1, keepdims=True)        # [T, 1]
    cum = _prefix_sum_last(sw)
    mid = cum - 0.5 * sw
    # breakpoints: xs = [0, mid_0..mid_{C-1}, tot], ys = [min, mean.., max]
    # (empty cells collapse onto (tot, max): identical to the XLA path)
    occupied = sw > 0
    xs = jnp.where(occupied, mid, tot)
    ys = jnp.where(occupied, skey, mx)
    # interval breakpoints are quantile-invariant: build the segment
    # tables once, only t/inside/seg vary per quantile
    x_lo = jnp.concatenate([jnp.zeros_like(tot), xs], axis=-1)
    x_hi = jnp.concatenate([xs, tot], axis=-1)
    y_lo = jnp.concatenate([mn, ys], axis=-1)
    y_hi = jnp.concatenate([ys, mx], axis=-1)
    denom = jnp.maximum(x_hi - x_lo, jnp.float32(1e-30))
    slope = (y_hi - y_lo) / denom
    for qi in range(n_q):
        t = qs_ref[qi] * tot                         # [T, 1]
        # interval [xs_k, xs_{k+1}) containing t, plus the two endpoint
        # segments; one-hot masks instead of a gather
        seg = y_lo + (t - x_lo) * slope
        inside = (t >= x_lo) & (t < x_hi)
        # t == tot falls outside every half-open interval: clamp to max
        any_inside = jnp.any(inside, axis=-1, keepdims=True)
        picked = jnp.sum(jnp.where(inside, seg, 0.0), axis=-1,
                         keepdims=True)
        # degenerate intervals (duplicate xs) can double-select; divide
        # by the selection count to keep the value (all dups are equal)
        n_sel = jnp.maximum(
            jnp.sum(inside.astype(jnp.float32), axis=-1, keepdims=True),
            1.0)
        v = jnp.where(any_inside, picked / n_sel, mx)
        v = jnp.where(tot > 0, v, jnp.float32(jnp.nan))
        out_ref[:, qi:qi + 1] = v


def quantiles_rows(mean, weight, mn, mx, qs, *, interpret: bool = False):
    """Pallas quantiles over rows: mean/weight f32[R, C], mn/mx f32[R],
    qs f32[Q] -> f32[R, Q]. R is padded to a ROW_TILE multiple and C to
    a power of two (pad cells carry weight 0)."""
    r, c = mean.shape
    n_q = int(qs.shape[0])
    c_pad = max(_next_pow2(c), 128)
    # Keep the per-step VMEM working set roughly constant as the cell
    # count grows (exact-extreme protection widened production rows to
    # 472 → c_pad 512): halve the row tile beyond 256 cells so the sort
    # temporaries stay well inside VMEM on first-silicon runs.
    row_tile = ROW_TILE if c_pad <= 256 else ROW_TILE // 2
    r_pad = ((r + row_tile - 1) // row_tile) * row_tile
    if c_pad != c or r_pad != r:
        mean = jnp.pad(mean, ((0, r_pad - r), (0, c_pad - c)))
        weight = jnp.pad(weight, ((0, r_pad - r), (0, c_pad - c)))
        mn = jnp.pad(mn, (0, r_pad - r))
        mx = jnp.pad(mx, (0, r_pad - r))
    grid = (r_pad // row_tile,)
    out = pl.pallas_call(
        functools.partial(_quantile_kernel, n_q=n_q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_q,), lambda i: (0,)),
            pl.BlockSpec((row_tile, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, n_q), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, n_q), jnp.float32),
        interpret=interpret,
        name="digest_quantiles",
    )(jnp.asarray(qs, jnp.float32), mean, weight,
      mn.reshape(-1, 1), mx.reshape(-1, 1))
    return out[:r]


# Module switch. True: the kernel compiles for the v5e at production
# widths (tests/test_tpu_compile.py) and agrees with the XLA path on the
# chip (chip_smoke.py, kernels phase).
ENABLED = True


def enabled() -> bool:
    """Use the Pallas path? Decided by the backend alone: on TPU the
    kernel runs (and a failure to compile or run raises — there is no
    fallback), on CPU the XLA path runs. Tests call `quantiles_rows`
    with interpret=True directly."""
    return ENABLED and jax.default_backend() == "tpu"
