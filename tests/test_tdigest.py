"""t-digest statistical validation, modeled on the reference's
tdigest/histo_test.go: quantile epsilon bounds on uniform data, weight
conservation, centroid capacity bound, merge fidelity."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veneur_tpu.ops import tdigest


def _feed(values, compression=100.0, chunk=4096):
    t = tdigest.empty_table((), compression=compression)
    values = np.asarray(values, np.float32)
    for i in range(0, len(values), chunk):
        v = values[i:i + chunk]
        pad = chunk - len(v)
        vv = np.pad(v, (0, pad))
        ww = np.pad(np.ones(len(v), np.float32), (0, pad))
        t = tdigest.add_batch_single(t, vv, ww, compression=compression)
    return t


def test_uniform_quantiles_within_reference_envelope():
    # reference histo_test.go:27 asserts median within 2% on U(0,1); BASELINE
    # demands <=1% p99 error at delta=100. Check a grid of quantiles.
    rng = np.random.RandomState(42)
    data = rng.uniform(0, 1, 100_000).astype(np.float32)
    t = _feed(data, compression=100.0)
    qs = np.array([0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99], np.float32)
    got = np.asarray(tdigest.quantiles(t, qs))
    exact = np.quantile(np.sort(data), qs)
    err = np.abs(got - exact)
    assert err[qs == 0.5][0] < 0.02, f"median err {err}"
    assert err[qs == 0.99][0] < 0.01, f"p99 err {err}"
    assert err[qs == 0.01][0] < 0.01, f"p1 err {err}"
    assert np.all(err < 0.02), f"errs {err}"


def test_weight_conservation_and_aggregates():
    rng = np.random.RandomState(7)
    data = rng.exponential(10.0, 50_000).astype(np.float32)
    t = _feed(data)
    total = float(t.count_hi + t.count_lo)
    assert total == pytest.approx(50_000, rel=1e-6)
    assert float(jnp.sum(t.weight)) == pytest.approx(50_000, rel=1e-5)
    assert float(t.min) == pytest.approx(data.min(), rel=1e-6)
    assert float(t.max) == pytest.approx(data.max(), rel=1e-6)
    assert float(t.sum_hi + t.sum_lo) == pytest.approx(data.sum(), rel=1e-4)
    assert float(t.recip_hi + t.recip_lo) == pytest.approx(
        (1.0 / data).sum(), rel=1e-3)


def test_merge_matches_single_digest():
    # reference histo_test.go sparse-merge test: merging shards stays within 2%
    rng = np.random.RandomState(3)
    data = rng.normal(100.0, 15.0, 80_000).astype(np.float32)
    whole = _feed(data)
    a = _feed(data[:40_000])
    b = _feed(data[40_000:])
    ab = np.stack([np.asarray(x) for x in (a.mean, b.mean)])
    # build a [2]-key table and merge row 0 with row 1
    ta = tdigest.TDigestTable(*[jnp.asarray(np.asarray(x))[None] for x in a])
    tb = tdigest.TDigestTable(*[jnp.asarray(np.asarray(x))[None] for x in b])
    merged = tdigest.merge_tables(ta, tb)
    qs = np.array([0.1, 0.5, 0.9, 0.99], np.float32)
    got = np.asarray(tdigest.quantiles(merged, qs))[0]
    ref = np.asarray(tdigest.quantiles(whole, qs))
    exact = np.quantile(data, qs)
    # merged digest within 1% relative of exact (value scale ~100)
    assert np.all(np.abs(got - exact) / np.abs(exact) < 0.01), (got, exact)
    assert np.all(np.abs(got - ref) / np.abs(exact) < 0.01), (got, ref)
    total = float(merged.count_hi[0] + merged.count_lo[0])
    assert total == pytest.approx(80_000, rel=1e-6)


def test_merge_is_deterministic_and_order_free():
    # unlike the reference (rand.Perm shuffle in Merge, merging_digest.go:376),
    # our merge is a pure function of the centroid multiset.
    rng = np.random.RandomState(11)
    a = _feed(rng.uniform(0, 1, 10_000))
    b = _feed(rng.uniform(5, 6, 10_000))
    ta = tdigest.TDigestTable(*[jnp.asarray(np.asarray(x))[None] for x in a])
    tb = tdigest.TDigestTable(*[jnp.asarray(np.asarray(x))[None] for x in b])
    m1 = tdigest.merge_tables(ta, tb)
    m2 = tdigest.merge_tables(tb, ta)
    np.testing.assert_allclose(np.asarray(m1.weight), np.asarray(m2.weight),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m1.mean), np.asarray(m2.mean),
                               rtol=1e-5, atol=1e-5)


def test_centroid_capacity_bound():
    # interior k-cells alone bound δ/2·cpk + 2; the full capacity adds
    # the 2·E protected extreme slots (exact-extreme protection)
    assert tdigest.interior_capacity(100.0, 2) >= 102
    assert tdigest.centroid_capacity(100.0, 2, 64) >= 102 + 128
    t = _feed(np.random.RandomState(0).uniform(0, 1, 20_000))
    occupied = int(jnp.sum(t.weight > 0))
    assert occupied <= tdigest.centroid_capacity()


def test_compress_invariants_weight_and_order():
    """reference tdigest/histo_test.go:55-76 validateMergingDigest, for
    the protected compress: total weight is conserved exactly through
    compression and merge, occupied cells are ascending-mean, interior
    cells respect the Δk bound, and the bottom/top E protected slots
    hold at most one input centroid each (exactness by construction)."""
    rng = np.random.RandomState(11)
    n = 4000
    vals = rng.lognormal(1.0, 1.2, n).astype(np.float32)
    wts = rng.randint(1, 4, n).astype(np.float32)
    m, w = tdigest.compress_rows(
        jnp.asarray(vals)[None, :], jnp.asarray(wts)[None, :])
    m, w = np.asarray(m)[0].astype(np.float64), \
        np.asarray(w)[0].astype(np.float64)
    occ = w > 0
    # weight conservation (f32 sums agree exactly: compression only
    # ADDS disjoint subsets of the same addends)
    np.testing.assert_allclose(w.sum(), float(wts.sum()), rtol=1e-6)
    # occupied means ascending in cell order
    mm = m[occ]
    assert np.all(np.diff(mm) >= 0)
    # protected ends are singletons: the E extreme input values appear
    # VERBATIM (bit-exact — singles scatter (m, w) directly, no
    # cumulative-diff or multiply/divide round-trip)
    E = tdigest.DEFAULT_EXACT_EXTREMES
    sv = np.sort(vals.astype(np.float64))
    np.testing.assert_array_equal(mm[:E], sv[:E])
    np.testing.assert_array_equal(mm[-E:], sv[-E:])
    # merging two compressed tables conserves weight too
    t1 = tdigest.empty_table(())._replace(
        mean=jnp.asarray(m, jnp.float32), weight=jnp.asarray(w, jnp.float32))
    merged = tdigest.merge_tables(t1, t1)
    np.testing.assert_allclose(float(np.asarray(merged.weight).sum()),
                               2 * float(wts.sum()), rtol=1e-6)


def _compress_case(name):
    """(mean, weight) f32[n, M] for one named input shape of compress_rows."""
    rng = np.random.RandomState(sum(map(ord, name)))
    n, m_len = 24, 560 if name == "merge-560" else 472
    mean = rng.lognormal(1.0, 1.2, (n, m_len)).astype(np.float32)
    weight = rng.randint(1, 4, (n, m_len)).astype(np.float32)
    if name == "sparse":
        fill = rng.choice([0.01, 0.05, 0.2, 0.4], n)
        weight *= rng.uniform(size=(n, m_len)) < fill[:, None]
    elif name == "empty-rows":
        weight[::2] = 0.0
        weight[1] = 0.0
        weight[1, 7] = 3.0                  # a row of one centroid
    elif name == "negative-means":
        mean[::2] *= -1.0                   # all negative
        mean[1::4] -= 3.0                   # straddling zero
    elif name == "heavy-row":
        # 2^20 of weight in the middle, weight-1 centroids at the ends: a
        # cumulative difference would cost the ends ulps of the TOTAL
        weight[:] = 1.0
        mean.sort(axis=1)
        weight[:, 200:280] = float(1 << 20) / 80
    return mean, weight


def _segment_reduce_f64(m, w, cell, out_c):
    """Float64 NumPy reference of the reduce: per row, each column's total
    weight, weighted mean and number of inputs, over the inputs with that
    cell (cell == out_c: an empty, dropped)."""
    n = m.shape[0]
    m, w = m.astype(np.float64), w.astype(np.float64)
    w_ref = np.zeros((n, out_c + 1))
    wm_ref = np.zeros((n, out_c + 1))
    awm_ref = np.zeros((n, out_c + 1))
    cnt = np.zeros((n, out_c + 1), np.int64)
    rows = np.broadcast_to(np.arange(n)[:, None], cell.shape)
    np.add.at(w_ref, (rows, cell), w)
    np.add.at(wm_ref, (rows, cell), w * m)
    np.add.at(awm_ref, (rows, cell), np.abs(w * m))
    np.add.at(cnt, (rows, cell), 1)
    w_ref, wm_ref, awm_ref, cnt = (a[:, :out_c]
                                   for a in (w_ref, wm_ref, awm_ref, cnt))
    safe = np.maximum(w_ref, 1e-300)
    return w_ref, wm_ref / safe, awm_ref / safe, cnt


@pytest.mark.parametrize("case", [
    "dense", "sparse", "empty-rows", "negative-means", "heavy-row",
    "merge-560", "vmap-vmap"])
def test_compress_rows_matches_float64_segment_reduce(case):
    """compress_rows against a float64 segment-reduce of the SAME cell
    assignment (tdigest._sorted_cells): occupancy equal, integer weights
    exact, means within a few f32 ulps of the cell's mean magnitude, a
    column of one input bit-exact, and the E bottom/top inputs verbatim."""
    mean, weight = _compress_case(case)
    out_c = tdigest.centroid_capacity()
    E = tdigest.DEFAULT_EXACT_EXTREMES
    compress = lambda m, w: tdigest.compress_rows(m, w, out_c=out_c)
    if case == "vmap-vmap":
        # the four-chip program's shape: vmap(vmap(compact_core))
        shaped = lambda a: jnp.asarray(a).reshape((2, 3, 4, a.shape[-1]))
        got = jax.vmap(jax.vmap(compress))(shaped(mean), shaped(weight))
        m_out, w_out = (np.asarray(a).reshape((-1, out_c)) for a in got)
    else:
        m_out, w_out = (np.asarray(a) for a in compress(mean, weight))
    m_s, w_s, cell = (np.asarray(a) for a in tdigest._sorted_cells(
        jnp.asarray(mean), jnp.asarray(weight),
        compression=tdigest.DEFAULT_COMPRESSION,
        cells_per_k=tdigest.DEFAULT_CELLS_PER_K, out_c=out_c,
        exact_extremes=E))
    assert np.all(np.diff(cell, axis=1) >= 0)    # sorted rows, sorted cells
    w_ref, m_ref, scale, cnt = _segment_reduce_f64(m_s, w_s, cell, out_c)

    # integer weights sum exactly, so occupancy is equal too
    np.testing.assert_array_equal(w_out.astype(np.float64), w_ref)
    occ = w_ref > 0
    assert np.all(m_out[~occ] == 0.0)
    ulp = np.finfo(np.float32).eps
    assert np.all(np.abs(m_out - m_ref)[occ] <= 4 * ulp * scale[occ])
    # a column of one input IS that input, mean and weight
    one = cnt == 1
    np.testing.assert_array_equal(m_out[one],
                                  m_ref[one].astype(np.float32))
    # the E lowest and E highest occupied inputs of every row, verbatim
    for r in range(mean.shape[0]):
        live = weight[r] > 0
        order = np.argsort(mean[r][live], kind="stable")
        sm, sw = mean[r][live][order], weight[r][live][order]
        k = min(E, len(sm))
        np.testing.assert_array_equal(m_out[r, :k], sm[:k])
        np.testing.assert_array_equal(w_out[r, :k], sw[:k])
        k_top = min(E, len(sm) - k)
        if k_top:
            np.testing.assert_array_equal(m_out[r, out_c - k_top:],
                                          sm[-k_top:])
            np.testing.assert_array_equal(w_out[r, out_c - k_top:],
                                          sw[-k_top:])


def test_compress_rows_lowers_without_scatter_or_gather():
    """The compiled compress_rows holds no scatter and no gather, and
    nothing the size of the [n, M, out_c] compare: the reduce runs in row
    blocks, so its temporaries do not grow with n·M·out_c. (The CPU
    backend does not fuse the compare into the reduce and holds one row
    block's product, REDUCE_ROW_BLOCK x M x out_c, a few times over; the
    TPU compiler fuses it — tests/test_tpu_compile.py holds that side.)"""
    m_len, out_c = 472, 280

    def compiled(n):
        x = jax.ShapeDtypeStruct((n, m_len), jnp.float32)
        return jax.jit(
            lambda m, w: tdigest.compress_rows(m, w, out_c=out_c)
        ).lower(x, x).compile()

    def op_names(c):
        return set(re.findall(r"[\s)]([a-z][a-z\-]*)\(", c.as_text()))

    # what one row block's compare costs the CPU backend: four products
    block = tdigest.REDUCE_ROW_BLOCK * m_len * out_c * 4
    for n in (64, 4096):
        c = compiled(n)
        ops = op_names(c)
        assert {"sort", "reduce"} <= ops, ops
        assert not {"scatter", "gather"} & ops, ops
        # [n, M] arrays and one row block's products: at 4096 rows that is
        # a twentieth of ONE [n, M, out_c] array
        assert c.memory_analysis().temp_size_in_bytes < (
            8 * n * m_len * 4 + 5 * block)


def test_cdf_roundtrip():
    rng = np.random.RandomState(5)
    data = rng.uniform(0, 1, 50_000).astype(np.float32)
    t = _feed(data)
    xs = np.array([0.1, 0.5, 0.9], np.float32)
    got = np.asarray(tdigest.cdf(t, xs))
    assert np.all(np.abs(got - xs) < 0.02), got


def test_empty_digest_quantile_is_nan():
    t = tdigest.empty_table(())
    q = np.asarray(tdigest.quantiles(t, np.array([0.5], np.float32)))
    assert np.isnan(q[0])


def test_single_sample():
    t = tdigest.empty_table(())
    t = tdigest.add_batch_single(
        t, np.array([42.0], np.float32), np.array([1.0], np.float32))
    q = np.asarray(tdigest.quantiles(t, np.array([0.0, 0.5, 1.0], np.float32)))
    np.testing.assert_allclose(q, [42.0, 42.0, 42.0], rtol=1e-6)


def test_weighted_samples_sample_rate():
    # 1/rate weighting semantics (reference samplers.go:484-494): a sample at
    # rate 0.1 counts as weight 10.
    t = tdigest.empty_table(())
    t = tdigest.add_batch_single(
        t, np.array([1.0, 2.0], np.float32), np.array([10.0, 30.0], np.float32))
    total = float(t.count_hi + t.count_lo)
    assert total == 40.0
    q = float(np.asarray(tdigest.quantiles(t, np.array([0.5], np.float32)))[0])
    assert 1.0 <= q <= 2.0
