"""Count-min sketch: heavy-hitter counting for unbounded tag cardinality.

No reference counterpart — this is the new sketch kernel BASELINE config 5
calls for (10M-tag SSF span firehose → top-K tag frequencies). Same
TPU-native shape as the other sketches (SURVEY §2.9): strings hash on the
host, the device holds a fixed [depth, width] counter table updated by one
batched scatter-add per ingest step, and estimates are a min-reduce over
depth gathered rows.

Guarantee (Cormode & Muthukrishnan): estimate >= true count, and
estimate <= true + eps*N with probability 1-delta for width >= e/eps,
depth >= ln(1/delta).
"""

from __future__ import annotations


from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu.utils.hashing import fnv1a_64, splitmix64

DEFAULT_DEPTH = 4
DEFAULT_WIDTH = 1 << 16


def _check_width(width: int):
    if width & (width - 1) or width <= 0:
        raise ValueError(f"count-min width must be a power of two, "
                         f"got {width} (column hashing masks low bits)")


def empty_counters(depth: int = DEFAULT_DEPTH, width: int = DEFAULT_WIDTH):
    _check_width(width)
    return jnp.zeros((depth, width), jnp.float32)


_M64 = (1 << 64) - 1


def _splitmix64_np(x: np.ndarray) -> np.ndarray:
    """Vectorized utils.hashing.splitmix64 (numpy uint64 wraps mod 2^64,
    matching the scalar's `& _M64`)."""
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def columns_for(member: bytes, depth: int = DEFAULT_DEPTH,
                width: int = DEFAULT_WIDTH) -> np.ndarray:
    """Host-side: the D column indices for one item. One 64-bit base hash,
    re-mixed per row — independent-enough row hashes without rehashing the
    bytes D times."""
    h = fnv1a_64(member)
    return np.asarray(
        [splitmix64(h ^ (0x9E3779B97F4A7C15 * (d + 1))) & (width - 1)
         for d in range(depth)], np.int64).astype(np.int32)


def columns_for_batch(members: List[bytes], depth: int = DEFAULT_DEPTH,
                      width: int = DEFAULT_WIDTH) -> np.ndarray:
    """Batch columns_for: one C call for the member hashes, numpy for the
    per-row remix (bit-identical to the scalar; asserted in tests). The
    per-member Python loop was the span firehose's top host cost."""
    from veneur_tpu import native
    if native.available():
        hs = native.hash64_batch(members)
    else:
        hs = np.asarray([fnv1a_64(m) for m in members], np.uint64)
    cols = np.empty((len(members), depth), np.int32)
    mask = np.uint64(width - 1)
    with np.errstate(over="ignore"):
        for d in range(depth):
            salt = np.uint64((0x9E3779B97F4A7C15 * (d + 1)) & _M64)
            cols[:, d] = (_splitmix64_np(hs ^ salt) & mask).astype(np.int32)
    return cols


@jax.jit
def insert_batch(counters, cols, weights):
    """counters f32[D, W], cols i32[B, D] (negative = padding, dropped),
    weights f32[B]. One flattened scatter-add for all D rows."""
    d, w = counters.shape
    b = cols.shape[0]
    rows = jnp.arange(d, dtype=jnp.int32)[None, :]        # [1, D]
    flat = jnp.where(cols >= 0, rows * w + cols, d * w)   # [B, D]
    upd = jnp.broadcast_to(weights[:, None], (b, d))
    out = counters.reshape(-1).at[flat.reshape(-1)].add(
        upd.reshape(-1), mode="drop")
    return out.reshape(d, w)


@jax.jit
def estimate(counters, cols):
    """Point estimates: min over depth of the gathered cells.
    counters f32[D, W], cols i32[B, D] -> f32[B]."""
    d = counters.shape[0]
    rows = jnp.arange(d, dtype=jnp.int32)[None, :]
    vals = counters[rows, jnp.maximum(cols, 0)]           # [B, D]
    return jnp.where((cols >= 0).all(axis=1), vals.min(axis=1), 0.0)


@jax.jit
def insert_and_estimate(counters, cols, weights):
    """insert_batch + estimate of the same items in ONE compiled program
    (one dispatch per batch instead of two — the update path always
    wants both)."""
    d, w = counters.shape
    b = cols.shape[0]
    rows = jnp.arange(d, dtype=jnp.int32)[None, :]
    flat = jnp.where(cols >= 0, rows * w + cols, d * w)
    upd = jnp.broadcast_to(weights[:, None], (b, d))
    out = counters.reshape(-1).at[flat.reshape(-1)].add(
        upd.reshape(-1), mode="drop").reshape(d, w)
    vals = out[rows, jnp.maximum(cols, 0)]
    est = jnp.where((cols >= 0).all(axis=1), vals.min(axis=1), 0.0)
    return out, est


@jax.jit
def merge(a, b):
    """Sketch union: counter-wise sum (mergeable like the other sketches —
    the global tier adds tables)."""
    return a + b


class HeavyHitters:
    """Host-side top-K tracking over a device sketch.

    Each batch: insert on device, estimate the batch's own items on device,
    then keep a bounded dict of the highest-estimate members (pruned to
    2K when it exceeds 4K). The sketch's one-sided error makes this a
    superset-biased top-K, which is the standard CMS heavy-hitter
    construction."""

    def __init__(self, k: int = 100, depth: int = DEFAULT_DEPTH,
                 width: int = DEFAULT_WIDTH):
        self.k = k
        self.depth = depth
        self.width = width
        self.counters = empty_counters(depth, width)
        self.candidates: Dict[bytes, float] = {}
        self.total = 0.0

    def update(self, members: List[bytes],
               weights: np.ndarray = None) -> None:
        if not members:
            return
        cols = jnp.asarray(columns_for_batch(members, self.depth,
                                             self.width))
        w = (np.ones(len(members), np.float32) if weights is None
             else np.asarray(weights, np.float32))
        self.counters, est = insert_and_estimate(self.counters, cols,
                                                 jnp.asarray(w))
        self.total += float(w.sum())
        est = np.asarray(est)
        for m, e in zip(members, est):
            self.candidates[m] = float(e)
        if len(self.candidates) > 4 * self.k:
            self._prune()

    def _prune(self):
        keep = sorted(self.candidates.items(), key=lambda kv: -kv[1])
        self.candidates = dict(keep[:2 * self.k])

    def top(self, k: int = None) -> List[Tuple[bytes, float]]:
        k = k or self.k
        return sorted(self.candidates.items(), key=lambda kv: -kv[1])[:k]

    def reset(self):
        self.counters = empty_counters(self.depth, self.width)
        self.candidates.clear()
        self.total = 0.0
