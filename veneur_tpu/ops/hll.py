"""Batched HyperLogLog for TPU.

The reference's Set sampler holds one axiomhq/hyperloglog sketch (2^14
registers) per set key and does Insert / Merge(union = register max) /
Estimate (reference samplers/samplers.go:367-463). Here a batch of sketches is
one uint8 array [..., R]:

- insert: the host hashes the member string to 64 bits with MetroHash64
  seed 1337 — the exact member hash of the reference's vendored sketch, so
  sketches union correctly across a mixed fleet — and ships
  (register_index, rho) pairs; the device does a deduplicated
  scatter-max (sort by register → segment-max → unique-index scatter),
- merge/union: elementwise ``maximum`` — which over a device mesh is exactly
  ``lax.pmax``, making the reference's global set-union (worker.go:438-495
  ImportMetricGRPC → Set.Merge) a single ICI collective,
- estimate: the classic HLL harmonic-mean estimator with linear counting for
  the small range, vectorized over keys.

Precision p=14 (R=16384) matches the reference's default
(samplers/samplers.go:383).

Round 8 adds a 6-bit *packed* register layout (FPGA HLL pipelines,
PAPERS.md arxiv 2005.13332): register values never exceed 64-p+1 = 51
at p=14, so 6 bits suffice and the resident table shrinks from
``uint8[K, 2^p]`` to ``int32[K, ceil(2^p*6/32)]`` words — register r
lives at bit offset 6·r little-endian within the word stream. Because
2^p is a multiple of 16 the pattern repeats exactly every 16 registers
/ 3 words (96 bits), which is what `pack_registers`/`unpack_registers`
exploit and what guarantees a straddling register's second word always
exists (the last register of each 16-group starts at in-word bit 26).
The packed table is what the device holds and what the fused Pallas
ingest kernel updates in place; `estimate`/`serialize` accept either
layout, and wire bytes are unchanged — packing is an at-rest layout,
not a wire format.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

DEFAULT_PRECISION = 14


def num_registers(precision: int = DEFAULT_PRECISION) -> int:
    return 1 << precision


def empty_registers(key_shape, precision: int = DEFAULT_PRECISION) -> jax.Array:
    key_shape = (key_shape,) if isinstance(key_shape, int) else tuple(key_shape)
    return jnp.zeros(key_shape + (num_registers(precision),), jnp.uint8)


def _alpha(m: int) -> float:
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1.0 + 1.079 / m)


def split_hash(hashes64, precision: int = DEFAULT_PRECISION):
    """Host-side helper: split uint64 hashes (as a numpy/int array) into
    (register index, rho) — rho = 1 + leading-zero-count of the remaining
    64-p bits, capped at 64-p+1."""
    import numpy as np
    h = np.asarray(hashes64, dtype=np.uint64)
    p = precision
    reg = (h >> np.uint64(64 - p)).astype(np.int32)
    rest = h << np.uint64(p)  # top 64-p payload bits in the high positions
    # rho = leading zeros of rest (within 64-p bits) + 1
    rho = np.zeros(h.shape, np.int32)
    cur = rest
    # binary leading-zero count on uint64
    lz = np.full(h.shape, 0, np.int32)
    for shift in (32, 16, 8, 4, 2, 1):
        mask = cur < (np.uint64(1) << np.uint64(64 - shift))
        lz = np.where(mask, lz + shift, lz)
        cur = np.where(mask, cur << np.uint64(shift), cur)
    lz = np.where(rest == 0, 64, lz)
    rho = np.minimum(lz, 64 - p) + 1
    return reg, rho.astype(np.uint8)


def _dedup_max(slot, reg, rho):
    """Sort a batch by (slot, register) and reduce every run of equal
    pairs to its max rho, so a scatter that follows has unique indices —
    the fast path on TPU. Returns (slot, reg, rho_max, is_last) in sorted
    order; `is_last` marks the one position of each run that carries the
    update."""
    order = jnp.lexsort((reg, slot))
    ss = slot[order]
    gs = reg[order]
    same = (ss[:-1] == ss[1:]) & (gs[:-1] == gs[1:])
    is_last = jnp.concatenate([~same, jnp.ones((1,), bool)])
    seg_start = jnp.concatenate([jnp.ones((1,), bool), ~same])
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    run_max = jax.ops.segment_max(rho[order].astype(jnp.int32), seg_id,
                                  num_segments=slot.shape[0],
                                  indices_are_sorted=True)
    return ss, gs, run_max[seg_id], is_last


@partial(jax.jit, static_argnames=("precision",))
def insert_batch(registers, slot, reg, rho, *, precision: int = DEFAULT_PRECISION):
    """Scatter-max a batch of (slot, register, rho) into registers [K, R].

    slot: i32[B] key-table slot (slot >= K → dropped padding),
    reg:  i32[B] register index in [0, R),
    rho:  u8[B] rank value.

    Dedup first (`_dedup_max`) so the final scatter has unique indices.
    """
    k = registers.shape[0]
    # 2D scatter indices (slot, reg) — avoids int32 overflow of a flattened
    # slot*R+reg index for large key tables (K*R can exceed 2^31).
    slot = jnp.where((slot >= 0) & (slot < k), slot, k)
    ss, gs, run_max, is_last = _dedup_max(slot, reg, rho)
    upd_slot = jnp.where(is_last, ss, k)
    upd_val = jnp.where(is_last, run_max.astype(jnp.uint8), 0)
    return registers.at[upd_slot, gs].max(upd_val, mode="drop")


def merge(a, b):
    """Union of two register tables (reference Set.Merge, samplers.go:461)."""
    return jnp.maximum(a, b)


@jax.jit
def merge_rows(registers, slot, rows):
    """Scatter-union imported register rows into a table: the global-tier
    HLL merge (reference worker.go:438 ImportMetricGRPC -> Set.Merge).
    registers u8[K, R], slot i32[B] (out-of-range = drop), rows u8[B, R]."""
    return registers.at[slot].max(rows, mode="drop")


# ---------------------------------------------------------------------------
# 6-bit packed register layout
# ---------------------------------------------------------------------------

REGISTER_BITS = 6        # max rho = 64-4+1 = 61 < 64 fits any p >= 4


def packed_words(precision: int = DEFAULT_PRECISION) -> int:
    """int32 words per key for the 6-bit packed layout."""
    return (num_registers(precision) * REGISTER_BITS + 31) // 32


def empty_registers_packed(key_shape,
                           precision: int = DEFAULT_PRECISION) -> jax.Array:
    key_shape = (key_shape,) if isinstance(key_shape, int) else tuple(key_shape)
    return jnp.zeros(key_shape + (packed_words(precision),), jnp.int32)


def _group16(x, last):
    """Reshape the trailing axis into (groups, last) 16-register groups.
    The group count is computed explicitly (not -1): a zero-row input —
    e.g. restoring a snapshot with no live sets — makes -1 unresolvable."""
    return x.reshape(x.shape[:-1] + (x.shape[-1] // last, last))


def pack_registers(regs, *, precision: int = DEFAULT_PRECISION) -> jax.Array:
    """u8[..., R] dense registers -> i32[..., W] 6-bit packed words.

    16 registers pack into exactly 3 words (96 bits), so the whole
    transform is shifts and ORs over a [..., R/16, 16] view — no scatter.
    Left shifts that cross bit 31 wrap (defined for lax shifts); the bit
    pattern is what matters.
    """
    r = num_registers(precision)
    assert r % 16 == 0 and regs.shape[-1] == r
    v = _group16(regs, 16).astype(jnp.int32) & 0x3F
    g = [v[..., i] for i in range(16)]
    w0 = (g[0] | (g[1] << 6) | (g[2] << 12) | (g[3] << 18) | (g[4] << 24)
          | ((g[5] & 0x3) << 30))
    w1 = ((g[5] >> 2) | (g[6] << 4) | (g[7] << 10) | (g[8] << 16)
          | (g[9] << 22) | ((g[10] & 0xF) << 28))
    w2 = ((g[10] >> 4) | (g[11] << 2) | (g[12] << 8) | (g[13] << 14)
          | (g[14] << 20) | (g[15] << 26))
    words = jnp.stack([w0, w1, w2], axis=-1)
    return words.reshape(regs.shape[:-1] + (packed_words(precision),))


def unpack_registers(words, *, precision: int = DEFAULT_PRECISION) -> jax.Array:
    """i32[..., W] packed words -> u8[..., R] dense registers.

    Right shifts on int32 are arithmetic (sign-extending); every lane is
    masked after the shift, so the sign bit never leaks into a register.
    """
    w = packed_words(precision)
    assert words.shape[-1] == w
    g = _group16(words, 3)
    w0, w1, w2 = g[..., 0], g[..., 1], g[..., 2]
    regs = [
        w0 & 0x3F, (w0 >> 6) & 0x3F, (w0 >> 12) & 0x3F, (w0 >> 18) & 0x3F,
        (w0 >> 24) & 0x3F,
        ((w0 >> 30) & 0x3) | ((w1 & 0xF) << 2),
        (w1 >> 4) & 0x3F, (w1 >> 10) & 0x3F, (w1 >> 16) & 0x3F,
        (w1 >> 22) & 0x3F,
        ((w1 >> 28) & 0xF) | ((w2 & 0x3) << 4),
        (w2 >> 2) & 0x3F, (w2 >> 8) & 0x3F, (w2 >> 14) & 0x3F,
        (w2 >> 20) & 0x3F, (w2 >> 26) & 0x3F,
    ]
    out = jnp.stack(regs, axis=-1)
    return out.reshape(words.shape[:-1]
                       + (num_registers(precision),)).astype(jnp.uint8)


def pack_registers_np(regs, precision: int = DEFAULT_PRECISION):
    """Host numpy twin of pack_registers (persistence / import staging)."""
    import numpy as np
    regs = np.asarray(regs, np.uint8)
    r = num_registers(precision)
    assert r % 16 == 0 and regs.shape[-1] == r
    v = _group16(regs, 16).astype(np.int64) & 0x3F
    g = [v[..., i] for i in range(16)]
    w0 = (g[0] | (g[1] << 6) | (g[2] << 12) | (g[3] << 18) | (g[4] << 24)
          | ((g[5] & 0x3) << 30))
    w1 = ((g[5] >> 2) | (g[6] << 4) | (g[7] << 10) | (g[8] << 16)
          | (g[9] << 22) | ((g[10] & 0xF) << 28))
    w2 = ((g[10] >> 4) | (g[11] << 2) | (g[12] << 8) | (g[13] << 14)
          | (g[14] << 20) | (g[15] << 26))
    words = np.stack([w0, w1, w2], axis=-1) & 0xFFFFFFFF
    return (words.reshape(regs.shape[:-1] + (packed_words(precision),))
            .astype(np.uint32).view(np.int32))


def unpack_registers_np(words, precision: int = DEFAULT_PRECISION):
    """Host numpy twin of unpack_registers."""
    import numpy as np
    words = np.asarray(words)
    w = packed_words(precision)
    assert words.shape[-1] == w
    u = (words.astype(np.int64) & 0xFFFFFFFF)
    g = _group16(u, 3)
    w0, w1, w2 = g[..., 0], g[..., 1], g[..., 2]
    regs = [
        w0 & 0x3F, (w0 >> 6) & 0x3F, (w0 >> 12) & 0x3F, (w0 >> 18) & 0x3F,
        (w0 >> 24) & 0x3F,
        ((w0 >> 30) & 0x3) | ((w1 & 0xF) << 2),
        (w1 >> 4) & 0x3F, (w1 >> 10) & 0x3F, (w1 >> 16) & 0x3F,
        (w1 >> 22) & 0x3F,
        ((w1 >> 28) & 0xF) | ((w2 & 0x3) << 4),
        (w2 >> 2) & 0x3F, (w2 >> 8) & 0x3F, (w2 >> 14) & 0x3F,
        (w2 >> 20) & 0x3F, (w2 >> 26) & 0x3F,
    ]
    out = np.stack(regs, axis=-1)
    return out.reshape(words.shape[:-1]
                       + (num_registers(precision),)).astype(np.uint8)


@partial(jax.jit, static_argnames=("precision",))
def insert_batch_packed(words, slot, reg, rho, *,
                        precision: int = DEFAULT_PRECISION):
    """`insert_batch` over the packed table, touching only the addressed
    words: dedup (slot, reg) by sort + segment-max, gather each
    register's one or two words, max the 6-bit field, and scatter-add
    the per-word field deltas back. Work and temporaries are O(batch),
    independent of the table size. Bit-identical to
    unpack -> `insert_batch` -> pack (register max commutes with
    packing; tests/test_hll.py pins it): fields are disjoint bit ranges,
    so adding ((new - cur) << shift) in wrapping i32 arithmetic rewrites
    exactly that field, and a word shared by several updated registers
    just sums their deltas. Out-of-range slots or registers —
    negative ones included — are dropped."""
    k, w = words.shape[-2], words.shape[-1]
    ok = ((slot >= 0) & (slot < k)
          & (reg >= 0) & (reg < num_registers(precision)))
    slot = jnp.where(ok, slot, k)
    reg = jnp.where(ok, reg, 0)
    ss, gs, run_max, is_last = _dedup_max(slot, reg, rho)

    bit = gs * REGISTER_BITS
    w0 = bit >> 5
    sh = bit & 31
    straddle = sh > 32 - REGISTER_BITS      # field continues in word w0+1
    nlo = jnp.where(straddle, 32 - sh, 0)   # field bits held by word w0
    sc = jnp.minimum(ss, k - 1)             # dropped rows gather junk,
    #                                         never written back
    w1 = jnp.minimum(w0 + 1, w - 1)
    lo = words[sc, w0]
    hi = words[sc, w1]
    cur = (jax.lax.shift_right_logical(lo, sh)
           | jnp.where(straddle, hi << nlo, 0)) & 0x3F
    new = jnp.maximum(cur, run_max) & 0x3F
    d_lo = (new - cur) << sh
    d_hi = (new >> nlo) - (cur >> nlo)
    tgt = jnp.where(is_last, ss, k)         # one update per (slot, reg)
    tgt_hi = jnp.where(straddle, tgt, k)
    return words.at[jnp.concatenate([tgt, tgt_hi]),
                    jnp.concatenate([w0, w1])].add(
        jnp.concatenate([d_lo, d_hi]), mode="drop")


@partial(jax.jit, static_argnames=("precision",))
def merge_rows_packed(words, slot, rows, *,
                      precision: int = DEFAULT_PRECISION):
    """`merge_rows` over the packed table: union dense u8 import rows into
    i32 packed words. Touches only the B addressed rows (gather -> unpack
    -> max -> pack -> unique-index set), not the whole table. Duplicate
    slots are combined host-order-free by a segment-max before the set,
    so the final `.set` has unique indices. Out-of-range slots —
    including negative ones — are dropped."""
    k = words.shape[0]
    slot = jnp.where((slot >= 0) & (slot < k), slot, k)
    order = jnp.argsort(slot)
    ss = slot[order]
    rs = rows[order].astype(jnp.int32)
    seg_start = jnp.concatenate([jnp.ones((1,), bool), ss[1:] != ss[:-1]])
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    combined = jax.ops.segment_max(rs, seg_id, num_segments=slot.shape[0],
                                   indices_are_sorted=True)
    upd = combined[seg_id].astype(jnp.uint8)       # per-position segment max
    tgt = jnp.where(seg_start, ss, k)              # unique: segment heads only
    cur = words[jnp.minimum(tgt, k - 1)]           # dropped rows gather junk,
    #                                                never written back
    merged = jnp.maximum(unpack_registers(cur, precision=precision), upd)
    packed = pack_registers(merged, precision=precision)
    return words.at[tgt].set(packed, mode="drop")


MAGIC = b"VHLL"          # legacy round-1 wire format (still decodable)
_SPARSE_PP = 25          # axiomhq sparse precision (hyperloglog.go pp)


def serialize(registers, precision: int = DEFAULT_PRECISION) -> bytes:
    """Wire bytes for one key's registers in the reference sketch's
    MarshalBinary layout (axiomhq/hyperloglog hyperloglog.go:274): dense
    form `[version=1][p][b][sparse=0][len(m/2) BE32][m/2 nibble-packed
    bytes]`, register value = b + stored nibble, register 2i in the high
    nibble of byte i. A reference global can UnmarshalBinary these bytes
    directly, so forwarded set metrics merge across a mixed fleet.

    Base selection mirrors the reference's rebase invariant (b only ever
    grows to the register minimum): exact whenever the register spread fits
    in a nibble, saturating at b+15 otherwise — the same tailcut loss the
    reference's own insert applies (hyperloglog.go:169-180).
    """
    import numpy as np
    regs = np.asarray(registers)
    if regs.dtype != np.uint8:           # 6-bit packed i32 row
        regs = unpack_registers_np(regs, precision)
    m = regs.shape[0]
    mn, mx = int(regs.min()), int(regs.max())
    b = 0
    if mn > 0 and mx > 15:
        b = min(mn, mx - 15)
    stored = np.clip(regs.astype(np.int32) - b, 0, 15).astype(np.uint8)
    packed = ((stored[0::2] << 4) | stored[1::2]).astype(np.uint8)
    return (bytes([1, precision, b, 0]) + (m // 2).to_bytes(4, "big")
            + packed.tobytes())


def _decode_sparse_hash(k: int, p: int):
    """axiomhq sparse.go decodeHash: sparse key -> (register, rho)."""
    pp = _SPARSE_PP
    if k & 1:
        r = ((k >> 1) & 0x3F) + pp - p
        idx = (k >> (32 - p)) & ((1 << p) - 1)
    else:
        shifted = (k << (32 - pp + p - 1)) & 0xFFFFFFFF
        # clz32(shifted) + 1; shifted==0 cannot occur for a valid key
        r = (33 - shifted.bit_length()) if shifted else 32
        idx = (k >> (pp - p + 1)) & ((1 << p) - 1)
    return idx, r


def _bitlen32(x):
    """Vectorized int.bit_length for non-negative int64 arrays < 2^32.
    Binary-search halving — no float log2 (exact at every power of two)."""
    import numpy as np
    x = x.astype(np.int64)
    n = np.zeros_like(x)
    for s in (16, 8, 4, 2, 1):
        big = x >= (np.int64(1) << s)
        n = np.where(big, n + s, n)
        x = np.where(big, x >> s, x)
    return n + (x > 0)


def _decode_sparse_hashes_np(keys, p: int):
    """Vectorized `_decode_sparse_hash` over an int64 key array — returns
    (idx, r) int64 arrays. Same field math as the scalar version (the
    sparse-form oracle test in tests/test_hll.py pins both)."""
    import numpy as np
    pp = _SPARSE_PP
    k = keys.astype(np.int64) & 0xFFFFFFFF
    m = 1 << p
    odd = (k & 1) == 1
    r_odd = ((k >> 1) & 0x3F) + pp - p
    idx_odd = (k >> (32 - p)) & (m - 1)
    shifted = (k << (32 - pp + p - 1)) & 0xFFFFFFFF
    r_even = np.where(shifted == 0, 32, 33 - _bitlen32(shifted))
    idx_even = (k >> (pp - p + 1)) & (m - 1)
    return (np.where(odd, idx_odd, idx_even),
            np.where(odd, r_odd, r_even))


def _decode_varint_deltas(buf: bytes):
    """Vectorized LEB128 varint decode of axiomhq's compressedList delta
    stream -> int64 delta array. Replaces the per-byte Python while loop
    (round-8 satellite; ~40x on a 16k-key sparse payload — see
    benchmarks/micro.py hll_codec_roundtrip).

    Grouping trick: a varint ends at each byte with the continuation bit
    clear; `np.add.reduceat` over per-byte `7*pos`-shifted payloads at the
    group starts reassembles every value in one pass."""
    import numpy as np
    if not buf:
        return np.zeros(0, np.int64)
    b = np.frombuffer(buf, np.uint8).astype(np.int64)
    is_end = (b & 0x80) == 0
    if not is_end[-1]:
        raise ValueError("truncated HLL sparse varint")
    ends = np.nonzero(is_end)[0]
    starts = np.concatenate([[0], ends[:-1] + 1])
    gid = np.cumsum(np.concatenate([[False], is_end[:-1]]).astype(np.int64))
    pos = np.arange(b.shape[0]) - starts[gid]
    if pos.max() * 7 >= 63:
        raise ValueError("HLL sparse varint too long")
    vals = (b & 0x7F) << (7 * pos)
    return np.add.reduceat(vals, starts)


def _deserialize_axiomhq(data: bytes):
    import numpy as np
    p = data[1]
    b = data[2]
    m = 1 << p
    if data[3] == 1:
        # sparse form: tmpSet (BE32 count + BE32 keys) then compressedList
        # (count, last, varint-delta list) — decode into dense registers,
        # exactly the sketch's own toNormal() conversion
        regs = np.zeros(m, np.uint8)
        (tssz,) = _be32(data, 4)
        if 8 + 4 * tssz + 12 > len(data):
            raise ValueError("truncated HLL sparse payload (tmpSet)")
        off = 8
        ts_keys = np.frombuffer(data[off:off + 4 * tssz], ">u4") \
            .astype(np.int64)
        off += 4 * tssz
        off += 8  # compressedList count + last (we re-derive from deltas)
        (sz,) = _be32(data, off)
        off += 4
        if off + sz > len(data):
            raise ValueError("truncated HLL sparse payload (list)")
        deltas = _decode_varint_deltas(data[off:off + sz])
        keys = np.concatenate([ts_keys, np.cumsum(deltas)])
        if keys.shape[0]:
            idx, r = _decode_sparse_hashes_np(keys, p)
            acc = np.zeros(m, np.int64)
            np.maximum.at(acc, idx, r)
            regs = acc.astype(np.uint8)
        return p, regs
    (sz,) = _be32(data, 4)
    packed = np.frombuffer(data[8:8 + sz], np.uint8)
    if packed.shape[0] != m // 2:
        raise ValueError("HLL dense payload length mismatch")
    regs = np.empty(m, np.uint8)
    regs[0::2] = packed >> 4
    regs[1::2] = packed & 0x0F
    if b:
        regs = (regs.astype(np.int32) + b).astype(np.uint8)
    return p, regs


def _be32(data: bytes, off: int):
    return (int.from_bytes(data[off:off + 4], "big"),)


def deserialize(data: bytes):
    """Parse sketch wire bytes -> (precision, uint8 registers[2^p]).

    Accepts the reference's axiomhq MarshalBinary bytes (dense AND sparse
    forms) and this framework's legacy VHLL dump."""
    import numpy as np
    if data[:4] == MAGIC:
        precision = data[4]
        regs = np.frombuffer(data[5:], np.uint8)
        if regs.shape[0] != (1 << precision):
            raise ValueError("HLL payload length mismatch")
        return precision, regs
    if len(data) >= 8 and data[0] == 1 and 4 <= data[1] <= 18:
        return _deserialize_axiomhq(data)
    raise ValueError("unrecognized HLL payload")


@partial(jax.jit, static_argnames=("precision",))
def estimate(registers, *, precision: int = DEFAULT_PRECISION):
    """Cardinality estimate per key: f32[...] over registers [..., R].

    Classic HLL: alpha·m²/Σ2^-M_j, with linear counting m·ln(m/V) when the
    raw estimate is below 5/2·m and zero registers exist. The reference's
    vendored lib uses the LogLog-Beta variant; both sit inside the ~0.8%
    standard error at p=14, which is what the tests assert.
    """
    if registers.dtype != jnp.uint8:     # 6-bit packed i32 table
        # fused lane-extraction path: no dense u8 register staging —
        # value-exact vs the dense math below (tests/test_query.py), so
        # flush exports and query-tier reads agree on every backend
        return estimate_packed_rows(registers, precision=precision)
    m = num_registers(precision)
    regs = registers.astype(jnp.float32)
    inv = jnp.sum(jnp.exp2(-regs), axis=-1)
    raw = _alpha(m) * m * m / inv
    zeros = jnp.sum((registers == 0).astype(jnp.float32), axis=-1)
    lin = m * jnp.log(m / jnp.maximum(zeros, 1.0))
    use_lin = (raw <= 2.5 * m) & (zeros > 0)
    return jnp.where(use_lin, lin, raw)


@partial(jax.jit, static_argnames=("precision",))
def estimate_packed_rows(words, *, precision: int = DEFAULT_PRECISION):
    """Cardinality estimate straight from 6-bit packed i32 rows [..., W].

    The lane shift/mask table (the 16-register/3-word group layout of
    `unpack_registers`) feeds the harmonic estimator directly, so the
    whole thing is one fused device program over the packed words — no
    dense u8[..., 2^p] register array is ever staged as a separate pass,
    and nothing crosses to the host. The register values, the f32
    conversion and the reduction layout are identical to running
    `estimate` on the unpacked table, so the result is value-exact vs
    the dense path (tests/test_query.py pins this) — which is also what
    keeps query-tier cardinalities equal to what the flush would export.
    """
    m = num_registers(precision)
    w = packed_words(precision)
    assert words.shape[-1] == w
    g = _group16(words, 3)
    w0, w1, w2 = g[..., 0], g[..., 1], g[..., 2]
    lanes = [
        w0 & 0x3F, (w0 >> 6) & 0x3F, (w0 >> 12) & 0x3F, (w0 >> 18) & 0x3F,
        (w0 >> 24) & 0x3F,
        ((w0 >> 30) & 0x3) | ((w1 & 0xF) << 2),
        (w1 >> 4) & 0x3F, (w1 >> 10) & 0x3F, (w1 >> 16) & 0x3F,
        (w1 >> 22) & 0x3F,
        ((w1 >> 28) & 0xF) | ((w2 & 0x3) << 4),
        (w2 >> 2) & 0x3F, (w2 >> 8) & 0x3F, (w2 >> 14) & 0x3F,
        (w2 >> 20) & 0x3F, (w2 >> 26) & 0x3F,
    ]
    regs_i = jnp.stack(lanes, axis=-1).reshape(words.shape[:-1] + (m,))
    regs = regs_i.astype(jnp.float32)
    inv = jnp.sum(jnp.exp2(-regs), axis=-1)
    raw = _alpha(m) * m * m / inv
    zeros = jnp.sum((regs_i == 0).astype(jnp.float32), axis=-1)
    lin = m * jnp.log(m / jnp.maximum(zeros, 1.0))
    use_lin = (raw <= 2.5 * m) & (zeros > 0)
    return jnp.where(use_lin, lin, raw)
