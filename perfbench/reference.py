"""The plain reference: what a straightforward reading of the DogStatsD
semantics says one flush interval of the stream must emit, and the
comparison that decides `correct`.

NumPy only; imports nothing of the program. An interval is the stream
positions [b0, b1) in datagrams of a pool that the sender cycles, so each
pool sample has a multiplicity in it. From that multiset: a counter is the
sum of increment / rate; a gauge is the value written last; a timer has an
exact count, min and max (float32, as the wire value is stored) and
midpoint-rank ("hazen") percentiles; a set is its number of distinct
members. `expected_forward` is the same for a global fed forwarded
sketches, the positions being RPCs.

A percentile is judged in rank space (`rank_errors`): how far the asked q
lies from the ranks that the emitted value holds among the interval's own
samples of that timer, as a share of their number. That does not grow
with the number of times the pool was cycled, so the limits do not depend
on the throughput a run reaches (PERF.md, section 2).
"""

from __future__ import annotations

import numpy as np

from typing import NamedTuple

from traffic import KINDS, ForwardPool, Pool


class TimerSamples(NamedTuple):
    """One interval's timer samples, sorted by timer and then by value."""
    ids: np.ndarray        # timer id of each run
    starts: np.ndarray     # offset of each run in `values`
    lens: np.ndarray       # samples in each run
    values: np.ndarray     # float32 wire values, held as float64


EXACT = ("rows_missing", "rows_extra", "rows_twice", "exact_mismatch",
         "tag_mismatch")


def multiplicity(b0: int, b1: int, n_datagrams: int):
    """How often each datagram of the pool lies in stream positions
    [b0, b1), and the last such position (-1 where there is none)."""
    d = np.arange(n_datagrams, dtype=np.int64)
    # positions p = d + j * n with b0 <= p < b1
    first_j = -(-(b0 - d) // n_datagrams)
    last_j = (b1 - 1 - d) // n_datagrams
    mult = np.maximum(last_j - first_j + 1, 0)
    last = np.where(mult > 0, d + last_j * n_datagrams, -1)
    return mult, last


def _segments(keys_sorted: np.ndarray):
    """Start offsets and lengths of the runs of equal keys."""
    starts = np.flatnonzero(np.r_[True, keys_sorted[1:] != keys_sorted[:-1]])
    return starts, np.diff(np.r_[starts, len(keys_sorted)])


def hazen(sorted_vals, starts, lens, q: float) -> np.ndarray:
    """Midpoint-rank quantile of each run: sample i of n sits at cumulative
    mass (i + 0.5) / n (NumPy's method="hazen")."""
    h = np.clip(lens * q - 0.5, 0, lens - 1)
    lo = np.floor(h).astype(np.int64)
    hi = np.minimum(lo + 1, lens - 1)
    frac = h - lo
    a, b = sorted_vals[starts + lo], sorted_vals[starts + hi]
    return a + (b - a) * frac


def _counter_rows(out: dict, prefix: str, ids, inc, m, counter_dtype) -> None:
    """Each counter's sum of inc x multiplicity. `counter_dtype` float32
    is one float, one add per sample as it arrives: the sum rounds once it
    passes 2^24."""
    if counter_dtype is np.float64:
        total = np.bincount(ids, weights=inc * m)
    else:
        total = np.zeros(ids.max() + 1, counter_dtype)
        np.add.at(total, np.repeat(ids, m),
                  np.repeat(inc, m).astype(counter_dtype))
    for i in np.unique(ids).tolist():
        out[f"{prefix}.c.{i:07d}"] = float(total[i])


def _timer_rows(out: dict, prefix: str, ids_x, val_x, percentiles,
                aggregates: bool) -> TimerSamples:
    """The rows of timers whose samples are ids_x, val_x, sorted by timer
    and then by value: min, max and count where `aggregates`, and the
    percentiles; and their TimerSamples."""
    starts, lens = _segments(ids_x)
    v32 = val_x.astype(np.float32)
    mn, mx = v32[starts], v32[starts + lens - 1]
    qs = [hazen(val_x, starts, lens, q) for q in percentiles]
    for j, i in enumerate(ids_x[starts].tolist()):
        base = f"{prefix}.t.{i:07d}"
        if aggregates:
            out[base + ".min"] = float(mn[j])
            out[base + ".max"] = float(mx[j])
            out[base + ".count"] = float(lens[j])
        for q, col in zip(percentiles, qs):
            out[f"{base}.{int(round(q * 100))}percentile"] = float(col[j])
    return TimerSamples(ids_x[starts], starts, lens, v32.astype(np.float64))


def expected(pool: Pool, b0: int, b1: int, percentiles,
             counter_dtype=np.float64):
    """name -> value for every row the sink must receive for the interval
    [b0, b1), and the interval's TimerSamples (None where it has no
    timer). `counter_dtype` is float64 as the configuration states; the
    control passes float32 (a counter kept in one float)."""
    mult_d, last_d = multiplicity(b0, b1, pool.n_datagrams)
    dg = np.arange(pool.n_samples) // pool.lines
    mult = mult_d[dg]
    # stream order of a sample's last occurrence in the interval
    last = last_d[dg] * pool.lines + np.arange(pool.n_samples) % pool.lines
    out = {}
    p = pool.prefix

    def of(kind):
        sel = np.flatnonzero((pool.kind == KINDS.index(kind)) & (mult > 0))
        return sel, pool.name[sel], pool.value[sel], mult[sel]

    sel, ids, val, m = of("counter")
    if len(sel):
        inc = val * np.where(pool.half_rate[sel], 2.0, 1.0)
        _counter_rows(out, p, ids, inc, m, counter_dtype)

    sel, ids, val, m = of("gauge")
    if len(sel):
        order = np.lexsort((last[sel], ids))
        starts, lens = _segments(ids[order])
        winners = order[starts + lens - 1]
        for i, v in zip(ids[winners].tolist(), val[winners].tolist()):
            out[f"{p}.g.{i:07d}"] = float(v)

    timers = None
    sel, ids, val, m = of("timer")
    if len(sel):
        ids_x, val_x = np.repeat(ids, m), np.repeat(val, m)
        order = np.lexsort((val_x, ids_x))
        timers = _timer_rows(out, p, ids_x[order], val_x[order], percentiles,
                             aggregates=True)

    sel, ids, val, m = of("set")
    if len(sel):
        span = int(val.max()) + 1
        pairs = np.unique(ids.astype(np.int64) * span + val.astype(np.int64))
        distinct = np.bincount(pairs // span)
        for i in np.flatnonzero(distinct).tolist():
            out[f"{p}.s.{i:07d}"] = float(distinct[i])
    return out, timers


def expected_forward(pool: ForwardPool, b0: int, b1: int, percentiles,
                     counter_dtype=np.float64):
    """`expected` for forwarded sketches at a global: the rows of the
    interval of RPC positions [b0, b1) of a forward pool, and its
    TimerSamples. An RPC's multiplicity is a datagram's (`multiplicity`).

    A counter is the exact sum of its forwarded values (float32 for the
    control: one add a forwarded value). A timer is held against the raw
    samples its digests summarise: count, min and max exact (float32, as
    stored), percentiles by rank (`rank_errors`) among the union of the
    interval's raw samples of that name. The rows follow upstream's rule
    at a global (stripe/veneur `worker.go:438-495` `ImportMetricGRPC`
    merges an imported digest into the histogram of its scope;
    `samplers.go:511-675` `Histo.Flush` and `flusher.go:61-77`: a mixed
    histogram emits its aggregates on the locals and its percentiles at
    the global only, a global one both at the global; the program's side
    is `server/flusher.py`'s `imported_only` rule): a `mixed` timer emits
    its percentiles only, a `global` timer its min, max and count too."""
    mult_m = multiplicity(b0, b1, pool.n_rpcs)[0][pool.metric_rpc]
    out, p = {}, pool.prefix
    sel = np.flatnonzero((pool.m_kind == KINDS.index("counter"))
                         & (mult_m > 0))
    if len(sel):
        _counter_rows(out, p, pool.m_name[sel],
                      pool.m_value[sel].astype(np.float64), mult_m[sel],
                      counter_dtype)
    timers = None
    name, value, metric = pool.timer_samples
    m = mult_m[metric]
    if m.any():
        # sorted by name and value already: repeating keeps the order
        timers = _timer_rows(out, p, np.repeat(name, m), np.repeat(value, m),
                             percentiles, pool.timer_scope == "global")
    return out, timers


def rank_errors(t: TimerSamples, got: np.ndarray, q: float) -> np.ndarray:
    """For each timer, how far q lies from the ranks that its emitted
    value `got` holds among the timer's n sorted samples, over n.

    Sample i sits at mass (i + 0.5) / n, as in `hazen`. A value equal to
    samples i0..i1 (ties) holds all their ranks. A value a share f of the
    way from a sample a to the next distinct sample b holds the ranks of
    a's ties moved the same share towards b's: with no ties that is the
    linear interpolation `hazen` inverts, and with the pool cycled c times
    (every value tied c times) it does not charge an interpolated value
    for the half step of c / n that no estimate can resolve. Below the
    least sample the rank is 0, above the largest 1. The value is first
    taken for the sample it lies within four float32 units of, if any."""
    n_runs, total = len(t.starts), len(t.values)
    if np.any(t.values < 0):
        raise ValueError("timer values are latencies, not negative")
    # one sorted key for all runs: run index * span + (value + 1)
    span = float(np.ceil(t.values.max())) + 4.0
    keys = np.repeat(np.arange(n_runs) * span, t.lens) + t.values + 1.0
    base = np.arange(n_runs) * span + 1.0
    g = np.where(np.isfinite(got), got, span)
    g = np.clip(g, -1.0, span - 3.0)
    # The answer is a float32: one within four units in its last place of
    # a sample is that sample. A digest's mean of tied samples rounds, so
    # a percentile that is the largest sample can come out a unit or two
    # above it, which is no rank error of 1 - q (PERF.md, section 2).
    tol = 4.0 * np.spacing(np.abs(g).astype(np.float32)).astype(np.float64)
    at = np.searchsorted(keys, base + g, "left") - t.starts
    under = t.values[np.clip(t.starts + at - 1, 0, total - 1)]
    over = t.values[np.clip(t.starts + at, 0, total - 1)]
    d_under = np.where(at > 0, g - under, np.inf)
    d_over = np.where(at < t.lens, over - g, np.inf)
    g = np.where((d_over <= tol) & (d_over <= d_under), over,
                 np.where(d_under <= tol, under, g))
    lo = np.searchsorted(keys, base + g, "left") - t.starts     # samples < g
    hi = np.searchsorted(keys, base + g, "right") - t.starts    # samples <= g
    a = t.values[np.clip(t.starts + lo - 1, 0, total - 1)]
    b = t.values[np.clip(t.starts + lo, 0, total - 1)]
    first_a = np.searchsorted(keys, base + a, "left") - t.starts
    last_b = np.searchsorted(keys, base + b, "right") - t.starts - 1
    with np.errstate(divide="ignore", invalid="ignore"):
        f = np.where(b > a, (g - a) / (b - a), 0.0)
    n = t.lens.astype(np.float64)
    tied = hi > lo
    f_lo = np.where(tied, lo, first_a + f * (lo - first_a)) + 0.5
    f_hi = np.where(tied, hi - 1, lo - 1 + f * (last_b - lo + 1)) + 0.5
    f_lo = np.where(lo == 0, 0.0, np.where(lo == t.lens, n, f_lo)) / n
    f_hi = np.where(hi == 0, 0.0, np.where(hi == t.lens, n, f_hi)) / n
    return np.maximum(0.0, np.maximum(f_lo - q, q - f_hi))


def hll_estimates(pool: Pool, b0: int, b1: int, precision: int) -> dict:
    """The control's sets: a plain HyperLogLog of 2^precision registers
    (splitmix64 of set and member, linear counting below 2.5 m), in place
    of the distinct count."""
    mult_d, _ = multiplicity(b0, b1, pool.n_datagrams)
    mult = mult_d[np.arange(pool.n_samples) // pool.lines]
    sel = np.flatnonzero((pool.kind == KINDS.index("set")) & (mult > 0))
    if not len(sel):
        return {}
    ids = pool.name[sel].astype(np.uint64)
    x = (ids << np.uint64(32)) | pool.value[sel].astype(np.uint64)
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    m = 1 << precision
    reg = (x >> np.uint64(64 - precision)).astype(np.int64)
    rest = x << np.uint64(precision)
    # rho: position of the first 1 bit of the remaining 64 - precision bits
    rho = np.full(len(x), 64 - precision + 1, np.int64)
    nz = rest != 0
    rho[nz] = 64 - np.floor(np.log2(rest[nz].astype(np.float64))).astype(
        np.int64)
    rho = np.minimum(rho, 64 - precision + 1)
    n_sets = int(ids.max()) + 1
    table = np.zeros((n_sets, m), np.int64)
    np.maximum.at(table, (ids.astype(np.int64), reg), rho)
    alpha = 0.7213 / (1 + 1.079 / m)
    raw = alpha * m * m / np.sum(2.0 ** -table, axis=1)
    zeros = np.sum(table == 0, axis=1)
    est = np.where((raw <= 2.5 * m) & (zeros > 0),
                   m * np.log(m / np.maximum(zeros, 1)), raw)
    return {f"{pool.prefix}.s.{i:07d}": float(np.round(est[i]))
            for i in np.unique(ids.astype(np.int64)).tolist()}


def pname(q: float) -> str:
    return f"p{int(round(q * 100))}"


def new_numbers(percentiles) -> dict:
    """The numbers compared, at nought. Per percentile the sample-weighted
    mean and the widest of the timers' rank errors; `_rel_max` and
    `set_err_max` are printed and never judged."""
    n = {k: 0 for k in EXACT}
    for q in percentiles:
        n.update({f"{pname(q)}_rank_wmean": 0.0, f"{pname(q)}_rank_max": 0.0,
                  f"{pname(q)}_rel_max": 0.0})
    n.update(set_err_mean=0.0, set_err_max=0.0)
    return n


def compare(got: dict, tags: dict, twice: int, want: dict, timers,
            percentiles, prefix: str, numbers: dict, examples: list,
            widest: dict | None = None) -> None:
    """Hold one interval's rows to the reference (`want` and `timers` as
    `expected` gives them); the worst of each number over the intervals
    compared so far is kept in `numbers`. `widest`, where one is given,
    gets for each percentile the timer behind this interval's widest rank
    error: what it emitted beside the exact value and the timer's largest
    sample, and how many timers lie over half that error with what share
    of the samples, so that a reading over its limit says at once whether
    one timer or a block of them carries it.

    Counters, gauges and a timer's count, min and max are exact. A
    percentile is held in rank space (`rank_errors`): `_rank_max` is the
    widest error of any timer, `_rank_wmean` the mean over the timers
    weighted by their samples, so that the hot timers, whose digests
    compress, carry it. set_err is |estimate - distinct| /
    max(distinct, 100): under ~100 members the estimator is linear
    counting, which loses one per register collision, so a small set is
    held to members, not to a share; its widest value is one collision in
    a small set and is printed, not judged."""
    missing = want.keys() - got.keys()
    extra = got.keys() - want.keys()
    numbers["rows_missing"] += len(missing)
    numbers["rows_extra"] += len(extra)
    numbers["rows_twice"] += twice
    for name in sorted(missing)[:2]:
        examples.append(f"missing {name}")
    for name in sorted(extra)[:2]:
        examples.append(f"unexpected {name}")
    set_errs = []
    kind_at = len(prefix) + 1
    for name, w in want.items():
        g = got.get(name)
        if g is None or name.endswith("percentile"):
            continue
        kind = name[kind_at]
        if kind == "s":
            set_errs.append(abs(g - w) / max(w, 100.0))
        elif g != w:
            numbers["exact_mismatch"] += 1
            if len(examples) < 8:
                examples.append(f"{name}: {g!r} != {w!r}")
        if kind == "c" and tags.get(name) != [f"k:{int(name[kind_at + 2:]) % 8}"]:
            numbers["tag_mismatch"] += 1

    def worst(name, value):
        numbers[name] = max(numbers[name], float(value))

    for q in percentiles if timers is not None else ():
        rows = [f"{prefix}.t.{i:07d}.{pname(q)[1:]}percentile"
                for i in timers.ids.tolist()]
        g = np.asarray([got.get(r, np.nan) for r in rows], np.float64)
        w = np.asarray([want[r] for r in rows])
        err = rank_errors(timers, g, q)
        worst(f"{pname(q)}_rank_wmean",
              np.sum(err * timers.lens) / np.sum(timers.lens))
        worst(f"{pname(q)}_rank_max", err.max())
        if widest is not None:
            j = int(err.argmax())
            near = err > 0.5 * err[j]
            widest[pname(q)] = {
                "err": float(err[j]), "timer": int(timers.ids[j]),
                "n": int(timers.lens[j]), "got": float(g[j]),
                "exact": float(w[j]),
                "max": float(timers.values[timers.starts[j]
                                           + timers.lens[j] - 1]),
                "timers_near": int(near.sum()),
                "their_sample_share": float(timers.lens[near].sum()
                                            / timers.lens.sum())}
        worst(f"{pname(q)}_rel_max", np.nanmax(np.abs(g - w) / np.abs(w)))
    if set_errs:
        worst("set_err_mean", np.mean(set_errs))
        worst("set_err_max", max(set_errs))


def verdict(numbers: dict, limits: dict):
    """[(name, value, limit, ok)] for every number compared, and whether
    all hold. The exact numbers always have the limit 0. A sketch number
    is compared only where the configuration gives it a limit: one whose
    control does not separate from the program in that deployment has
    none, and is printed but not judged (PERF.md, section 2)."""
    rows = []
    for name, value in numbers.items():
        if name in EXACT:
            rows.append((name, value, 0, value == 0))
        elif name in limits:
            rows.append((name, value, limits[name], value <= limits[name]))
    return rows, all(r[3] for r in rows)
