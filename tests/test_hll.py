"""HyperLogLog accuracy + merge tests (reference samplers Set semantics,
samplers/samplers_test.go set cases). Standard error at p=14 is ~0.8%;
assert estimates within 3% (≈4 sigma)."""

import numpy as np
import pytest

import jax.numpy as jnp

from veneur_tpu.ops import hll


def _hash64(ints):
    # splitmix64 — host-side stand-in for the reference's metrohash
    x = np.asarray(ints, np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    z = x
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return z


def _insert_ints(regs, slot_idx, ints):
    reg, rho = hll.split_hash(_hash64(ints))
    slot = np.full(len(ints), slot_idx, np.int32)
    return hll.insert_batch(regs, jnp.asarray(slot), jnp.asarray(reg),
                            jnp.asarray(rho))


def test_estimate_accuracy_various_cardinalities():
    for true_n in (100, 10_000, 200_000):
        regs = hll.empty_registers(1)
        regs = _insert_ints(regs, 0, np.arange(true_n))
        est = float(np.asarray(hll.estimate(regs))[0])
        assert abs(est - true_n) / true_n < 0.03, (true_n, est)


def test_duplicates_do_not_inflate():
    regs = hll.empty_registers(1)
    ints = np.concatenate([np.arange(5000)] * 4)
    regs = _insert_ints(regs, 0, ints)
    est = float(np.asarray(hll.estimate(regs))[0])
    assert abs(est - 5000) / 5000 < 0.03, est


def test_merge_is_union():
    # reference Set.Merge = HLL union (samplers.go:461)
    a = hll.empty_registers(1)
    b = hll.empty_registers(1)
    a = _insert_ints(a, 0, np.arange(0, 60_000))
    b = _insert_ints(b, 0, np.arange(40_000, 100_000))
    m = hll.merge(a, b)
    est = float(np.asarray(hll.estimate(m))[0])
    assert abs(est - 100_000) / 100_000 < 0.03, est


def test_multi_key_isolation():
    # inserts to one slot must not leak into another
    regs = hll.empty_registers(4)
    regs = _insert_ints(regs, 1, np.arange(10_000))
    regs = _insert_ints(regs, 3, np.arange(500))
    est = np.asarray(hll.estimate(regs))
    assert est[0] == 0.0 and est[2] == 0.0
    assert abs(est[1] - 10_000) / 10_000 < 0.03
    assert abs(est[3] - 500) / 500 < 0.05


def test_out_of_range_slot_dropped():
    regs = hll.empty_registers(2)
    reg, rho = hll.split_hash(_hash64(np.arange(100)))
    slot = np.full(100, 7, np.int32)  # out of range → padding
    out = hll.insert_batch(regs, jnp.asarray(slot), jnp.asarray(reg),
                           jnp.asarray(rho))
    assert float(jnp.sum(out)) == 0.0


@pytest.mark.parametrize("p", [4, 8, 14])
def test_packed_insert_bit_identical_to_dense_roundtrip(p):
    """insert_batch_packed touches only the addressed words; its result
    must be the dense path's, bit for bit: pack(insert_batch(unpack)).
    Batches carry duplicate (slot, register) pairs, several registers of
    one word, both word-straddling fields, out-of-range and negative
    slots, and rho beyond 6 bits (the dense path masks at pack time)."""
    rng = np.random.default_rng(p)
    k, r, b = 16, 1 << p, 512
    words = jnp.asarray(hll.pack_registers_np(
        rng.integers(0, 62, (k, r)).astype(np.uint8), p))
    for trial in range(4):
        slot = rng.integers(-2, k + 3, b).astype(np.int32)
        reg = rng.integers(0, min(r, 40) if trial % 2 else r,
                           b).astype(np.int32)
        rho = rng.integers(0, 64 if trial < 3 else 256, b).astype(np.uint8)
        got = hll.insert_batch_packed(words, slot, reg, rho, precision=p)
        dense = hll.insert_batch(hll.unpack_registers(words, precision=p),
                                 slot, reg, rho, precision=p)
        want = hll.pack_registers(dense, precision=p)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        words = got


# -- reference (axiomhq) wire-format compatibility --------------------------

def test_serialize_axiomhq_dense_layout():
    """serialize() emits the reference sketch's MarshalBinary dense layout:
    [version=1][p][b][sparse=0][m/2 BE32][nibble-packed], register 2i in
    the high nibble (hyperloglog.go:274-319, registers.go reg.set)."""
    rng = np.random.default_rng(7)
    regs = rng.integers(0, 14, size=1 << 14).astype(np.uint8)
    data = hll.serialize(regs, 14)
    assert data[0] == 1          # version
    assert data[1] == 14         # p
    assert data[2] == 0          # b (min register is 0)
    assert data[3] == 0          # dense
    assert int.from_bytes(data[4:8], "big") == (1 << 14) // 2
    body = np.frombuffer(data[8:], np.uint8)
    np.testing.assert_array_equal(body >> 4, regs[0::2])
    np.testing.assert_array_equal(body & 0x0F, regs[1::2])


def test_serialize_roundtrip_exact_small_values():
    rng = np.random.default_rng(8)
    regs = rng.integers(0, 16, size=1 << 14).astype(np.uint8)
    p, back = hll.deserialize(hll.serialize(regs, 14))
    assert p == 14
    np.testing.assert_array_equal(back, regs)


def test_serialize_roundtrip_rebased_large_values():
    # all registers nonzero with spread <= 15: base-rebased, still exact
    rng = np.random.default_rng(9)
    regs = rng.integers(11, 25, size=1 << 14).astype(np.uint8)
    data = hll.serialize(regs, 14)
    assert data[2] > 0  # base engaged
    p, back = hll.deserialize(data)
    np.testing.assert_array_equal(back, regs)


def test_serialize_saturates_like_reference_insert():
    # a zero register forces b=0; rho > 15 tailcuts at 15 exactly as the
    # reference's insert clamp (hyperloglog.go:169-180 capacity-1)
    regs = np.zeros(1 << 14, np.uint8)
    regs[5] = 40
    regs[6] = 3
    p, back = hll.deserialize(hll.serialize(regs, 14))
    assert back[5] == 15
    assert back[6] == 3
    assert back[0] == 0


def test_deserialize_sparse_form():
    """Hand-build a sparse MarshalBinary payload (tmpSet + compressedList,
    sparse.go:54 / compressed.go:55) and check it lands in the right
    registers with the right rho."""
    from veneur_tpu.utils.hashing import metro_hash_64

    members = [b"user-%d" % i for i in range(30)]
    hashes = [metro_hash_64(m) for m in members]
    p, pp = 14, 25

    def encode_hash(x):
        # sparse.go encodeHash
        idx = (x >> (64 - pp)) & ((1 << pp) - 1)
        if (x >> (64 - pp)) & ((1 << (pp - p)) - 1) == 0:
            low = (x & ((1 << (64 - pp)) - 1)) << pp
            w = low | (1 << (pp - 1))
            zeros = (64 - w.bit_length()) + 1 if w else 64
            return (idx << 7) | (zeros << 1) | 1
        return idx << 1

    keys = sorted({encode_hash(x) for x in hashes})
    # half in tmpSet, half in the compressed (delta-varint) list
    tmp, lst = keys[::2], keys[1::2]
    payload = bytes([1, p, 0, 1])
    payload += len(tmp).to_bytes(4, "big")
    for k in tmp:
        payload += k.to_bytes(4, "big")
    body = b""
    last = 0
    for k in lst:
        delta = k - last
        while delta & ~0x7F:
            body += bytes([(delta & 0x7F) | 0x80])
            delta >>= 7
        body += bytes([delta & 0x7F])
        last = k
    payload += len(lst).to_bytes(4, "big") + last.to_bytes(4, "big")
    payload += len(body).to_bytes(4, "big") + body

    got_p, regs = hll.deserialize(payload)
    assert got_p == p
    # oracle: direct dense insert of the same members
    from veneur_tpu.utils.hashing import hll_reg_rho
    want = np.zeros(1 << p, np.uint8)
    for m in members:
        reg, rho = hll_reg_rho(m, p)
        want[reg] = max(want[reg], rho)
    np.testing.assert_array_equal(regs, want)


def test_legacy_vhll_still_decodes():
    regs = np.arange(1 << 14, dtype=np.uint8) % 13
    data = hll.MAGIC + bytes([14]) + regs.tobytes()
    p, back = hll.deserialize(data)
    assert p == 14
    np.testing.assert_array_equal(back, regs)
