"""Pallas TPU kernel for the history tier's masked HLL window merge.

A range query merges, per requested step, every selected ring column of
each matched set key: the register max over the selected columns of
6-bit packed registers. The XLA chain (history/merge.py
_merge_windows_xla) stages a dense register block per column through
HBM on every fori step; rows are independent and a row tile of packed
words fits in VMEM, so the kernel keeps one packed accumulator per step
on-chip, visits each ring column once (the accumulate-over-last-grid-
axis pattern), skips the columns a step does not select, and takes the
register max on the packed words directly (`_packed_max`).

Selection is `enabled()`: the backend alone decides. Parity with the
XLA chain is asserted bit-exactly (packed words are integers) in
tests/test_history.py in interpret mode, which runs this same kernel on
CPU, and on the chip by chip_smoke.py; tests/test_tpu_compile.py
compiles it for the v5e at p=14.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veneur_tpu.ops import hll

ROW_TILE = 8     # set rows per grid step: one sublane tile of i32 words


def _packed_max(a, b):
    """Register-wise max of two 6-bit packed word arrays i32[T, W],
    computed on the packed words themselves — elementwise shifts and
    masks plus one lane rotation each way, no reshape to a register
    view (Mosaic refuses the [.., W/3, 3] interleaved reshape that
    hll.unpack_registers needs).

    Layout (ops/hll.py): 16 registers per 3 words. A word of lane type
    t = lane % 3 owns the fields that START in it, at in-word bits
    base_t + 6k with base = (0, 4, 2): six, five and five fields. The
    last field of a type-0 word (bit 30) and of a type-1 word (bit 28)
    continues in the next word's low bits; a type-2 word's fields all
    fit, so nothing ever crosses a 3-word group or wraps around the
    row."""
    w = a.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1)
    t = lane % 3
    base = jnp.where(t == 0, 0, jnp.where(t == 1, 4, 2))

    def rot(x, j):   # position i sees x[(i + j) % w]
        return jnp.concatenate([x[..., j:], x[..., :j]], axis=-1)

    a_next, b_next = rot(a, 1), rot(b, 1)
    out = jnp.zeros_like(a)
    carry = jnp.zeros_like(a)     # a straddling field's bits for word l+1
    for k in range(6):
        sh = base + 6 * k
        valid = sh < 32
        sh = jnp.minimum(sh, 31)
        straddle = valid & (sh > 32 - hll.REGISTER_BITS)
        nlo = jnp.where(straddle, 32 - sh, 0)    # field bits in this word

        def field(x, x_next):
            lo = jax.lax.shift_right_logical(x, sh)
            return (lo | jnp.where(straddle, x_next << nlo, 0)) & 0x3F

        m = jnp.where(valid,
                      jnp.maximum(field(a, a_next), field(b, b_next)), 0)
        out = out | (m << sh)                    # wraps: low bits only
        carry = carry | jnp.where(straddle, m >> nlo, 0)
    return out | rot(carry, w - 1)


def _merge_kernel(sel_ref, rows_ref, out_ref, *, n_steps: int):
    """Grid (row tile i, ring column j). rows_ref: i32[T, nw], column j
    of this row tile; out_ref: i32[S, T, nw], resident across j;
    sel_ref: i32[S, W] in SMEM, nonzero = step s selects column j."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)   # the register-max identity

    for s in range(n_steps):
        @pl.when(sel_ref[s, j] != 0)
        def _merge(s=s):
            out_ref[s] = _packed_max(out_ref[s], rows_ref[...])


def merge_windows_packed(rows, sel, *, precision: int,
                         interpret: bool = False):
    """rows i32[N, W, nw] packed HLL windows, sel f32[S, W] selection
    masks -> i32[N, S, nw]: per step, the packed register max over the
    selected columns. Pads rows with zeros (the register-max identity),
    so padding never changes an estimate."""
    n, w, nw = rows.shape
    assert nw == hll.packed_words(precision)
    s = int(sel.shape[0])
    n_pad = -(-n // ROW_TILE) * ROW_TILE
    if n_pad != n:
        rows = jnp.pad(rows, ((0, n_pad - n), (0, 0), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_merge_kernel, n_steps=s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_pad // ROW_TILE, w),
            in_specs=[pl.BlockSpec((None, ROW_TILE, nw),
                                   lambda i, j, sel: (j, i, 0))],
            out_specs=pl.BlockSpec((s, ROW_TILE, nw),
                                   lambda i, j, sel: (0, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((s, n_pad, nw), jnp.int32),
        interpret=interpret,
        name="history_hll_merge",
    # column-major [W, N, nw]: a block's last two dims must be a whole
    # (8, 128)-tiled slab, so the squeezed column dim has to lead
    )((sel > 0.0).astype(jnp.int32), rows.transpose(1, 0, 2))
    return out.transpose(1, 0, 2)[:n]


# Module switch, see `enabled`.
ENABLED = True


def enabled() -> bool:
    """Use the Pallas merge? Decided by the backend alone: on TPU the
    kernel runs (a failure to compile or run raises — no fallback), on
    CPU the XLA chain runs. Tests call `merge_windows_packed` with
    interpret=True directly."""
    return ENABLED and jax.default_backend() == "tpu"
