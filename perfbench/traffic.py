"""The one traffic generator: a traffic file's parameters and a seed give
a pool, cycled by a child process that imports nothing of JAX or the
program. The parent and the child each build the pool from the same file
and seed; the pool's `digest()` is compared so that the two can never
disagree in silence. Pure NumPy and stdlib.

A traffic file (`perfbench/traffic/<mix>.json`) states its way in,
`ingress`: `udp` (the default) or `forward`.

`udp`: a pool of DogStatsD samples, cut into datagrams (`sender.py`).
Per kind `names`, `samples` in the pool and the Zipf exponent `zipf_s`.
The first `names` samples of a kind cover each name once, so every
interval of at least one pool cycle touches every name and the flush's
row count does not follow the throughput; the rest are drawn Zipf(s) over
a seeded permutation of the names. Every seed gives the same sizes,
another order and other draws.

`forward`: what `locals` local agents forward to one global over gRPC
(`forwarder.py`), `bursts` flush intervals of the whole fleet in the
pool. Per kind (`counter`: global scope, one summed value a name;
`timer`: `scope` `mixed` or `global`, one t-digest a name) the fleet has
`names`; each local holds `names_per_local` of them for good: its
round-robin share of the fleet (name i belongs to local i mod `locals`),
so that every fleet name is forwarded in every burst, topped up by a
draw without replacement weighted Zipf(`zipf_s`) over the fleet's
seeded ranks. Each burst a local takes `samples_per_local` samples of a
kind: the first cover each of its names once, the rest are drawn Zipf
over the same ranks, so a name hot in the fleet is hot in every local.
A local's burst is its metrics in a seeded order, cut into RPCs of
`metrics_per_rpc`; a burst's locals come in a seeded order. Every seed
gives the same numbers of metrics, RPCs and samples.
"""

from __future__ import annotations

import functools
import hashlib
import json
from dataclasses import dataclass

import numpy as np

KINDS = ("counter", "gauge", "timer", "set")
LETTER = {"counter": "c", "gauge": "g", "timer": "t", "set": "s"}
WIRE = {"counter": "c", "gauge": "g", "timer": "ms", "set": "s"}
FORWARD_KINDS = ("counter", "timer")
SCOPES = ("mixed", "global")


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    ingress = spec.get("ingress", "udp")
    if ingress == "forward":
        _check_forward(path, spec)
        return spec
    if ingress != "udp":
        raise ValueError(f"{path}: unknown ingress {ingress!r} "
                         "(udp or forward)")
    for kind in spec["kinds"]:
        if kind not in KINDS:
            raise ValueError(f"{path}: unknown kind {kind!r}")
        k = spec["kinds"][kind]
        if k["samples"] < k["names"]:
            raise ValueError(f"{path}: {kind} has fewer samples than names")
    return spec


def _check_forward(path: str, spec: dict) -> None:
    for key in ("locals", "bursts", "metrics_per_rpc"):
        if int(spec[key]) < 1:
            raise ValueError(f"{path}: {key} must be at least 1")
    if float(spec["compression"]) <= 0:
        raise ValueError(f"{path}: compression must be positive")
    if not spec["kinds"]:
        raise ValueError(f"{path}: a forward mix needs a kind")
    for kind, k in spec["kinds"].items():
        if kind in ("set", "gauge"):
            raise ValueError(
                f"{path}: {kind}s are not forwarded by this generator yet "
                "(future work: a set needs the HyperLogLog wire encoding, a "
                "gauge a last-write order across locals)")
        if kind not in FORWARD_KINDS:
            raise ValueError(f"{path}: unknown kind {kind!r}")
        n, per = int(k["names"]), int(k["names_per_local"])
        if not -(-n // int(spec["locals"])) <= per <= n:
            raise ValueError(
                f"{path}: {kind} names_per_local must hold the local's "
                f"round-robin share of the {n} names and not exceed them")
        if int(k["samples_per_local"]) < per:
            raise ValueError(f"{path}: {kind} has fewer samples_per_local "
                             "than names_per_local")
        if kind == "timer" and k.get("scope") not in SCOPES:
            raise ValueError(f"{path}: timer scope must be one of {SCOPES}")


@dataclass
class Pool:
    """The pool in stream order (already shuffled). Sample i is line
    `i % lines` of datagram `i // lines`."""
    prefix: str
    lines: int                 # samples to a datagram
    kind: np.ndarray           # int8 index into KINDS
    name: np.ndarray           # int32 name id within its kind
    value: np.ndarray          # float64: increment, gauge value, latency, member
    half_rate: np.ndarray      # bool: counter sample sent with |@0.5
    names_per_kind: dict       # kind -> number of names

    @property
    def n_samples(self) -> int:
        return len(self.kind)

    @property
    def n_datagrams(self) -> int:
        return -(-len(self.kind) // self.lines)

    def datagram_sizes(self) -> np.ndarray:
        sizes = np.full(self.n_datagrams, self.lines, np.int64)
        sizes[-1] = self.n_samples - self.lines * (self.n_datagrams - 1)
        return sizes

    def rows_per_flush(self, n_percentiles: int, n_aggregates: int) -> int:
        n = self.names_per_kind
        return (n.get("counter", 0) + n.get("gauge", 0) + n.get("set", 0)
                + n.get("timer", 0) * (n_percentiles + n_aggregates))

    def text_lines(self) -> list:
        out = [None] * self.n_samples
        p = self.prefix
        for ki, kind in enumerate(KINDS):
            idx = np.flatnonzero(self.kind == ki)
            if not len(idx):
                continue
            ids = self.name[idx].tolist()
            vals = self.value[idx]
            if kind == "counter":
                half = self.half_rate[idx].tolist()
                for j, i, v, h in zip(idx.tolist(), ids,
                                      vals.astype(np.int64).tolist(), half):
                    out[j] = (f"{p}.c.{i:07d}:{v}|c|@0.5|#k:{i % 8}" if h
                              else f"{p}.c.{i:07d}:{v}|c|#k:{i % 8}")
            elif kind == "gauge":
                for j, i, v in zip(idx.tolist(), ids, vals.tolist()):
                    out[j] = f"{p}.g.{i:07d}:{v}|g"
            elif kind == "timer":
                for j, i, v in zip(idx.tolist(), ids, vals.tolist()):
                    out[j] = f"{p}.t.{i:07d}:{v:.3f}|ms"
            else:
                for j, i, v in zip(idx.tolist(), ids,
                                   vals.astype(np.int64).tolist()):
                    out[j] = f"{p}.s.{i:07d}:m{v}|s"
        return out

    def datagrams(self) -> list:
        lines, n = self.text_lines(), self.lines
        return [("\n".join(lines[i:i + n])).encode()
                for i in range(0, len(lines), n)]

    def digest(self) -> str:
        h = hashlib.sha256()
        for a in (self.kind, self.name, self.value, self.half_rate):
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()[:16]


def _zipf_draws(rng, n_names: int, n_draws: int, s: float) -> np.ndarray:
    """`n_draws` name ids, rank r drawn with probability ~ 1/r**s, ranks
    laid over a seeded permutation of the names."""
    if n_draws == 0:
        return np.zeros(0, np.int64)
    p = 1.0 / np.arange(1, n_names + 1, dtype=np.float64) ** s
    ranks = rng.choice(n_names, size=n_draws, p=p / p.sum())
    return rng.permutation(n_names)[ranks]


def build_pool(spec: dict, seed: int) -> Pool:
    kinds, names, values, half = [], [], [], []
    for ki, kind in enumerate(KINDS):
        k = spec["kinds"].get(kind)
        if not k:
            continue
        rng = np.random.default_rng([int(seed), 0x7062, ki])
        n, m = int(k["names"]), int(k["samples"])
        ids = np.concatenate([np.arange(n),
                              _zipf_draws(rng, n, m - n, float(k["zipf_s"]))])
        h = np.zeros(m, bool)
        if kind == "counter":
            v = rng.integers(1, 1000, m).astype(np.float64)
            h[n:] = rng.random(m - n) < float(k.get("half_rate_share", 0.0))
        elif kind == "gauge":
            # quarter steps: exact in f32 and in the decimal wire text
            v = rng.integers(-4000, 4000, m) / 4.0
        elif kind == "timer":
            v = np.round(rng.gamma(2.0, 15.0, m) + 0.5, 3)
        else:
            # members drawn with repeats from 4 x (the set's samples in the
            # pool) values: the distinct count stays below the inserts
            per_set = np.bincount(ids, minlength=n)
            v = np.floor(rng.random(m) * 4 * per_set[ids])
        kinds.append(np.full(m, ki, np.int8))
        names.append(ids.astype(np.int32))
        values.append(v)
        half.append(h)
    kind_a, name_a = np.concatenate(kinds), np.concatenate(names)
    value_a, half_a = np.concatenate(values), np.concatenate(half)
    order = np.random.default_rng([int(seed), 0x7062, 99]).permutation(
        len(kind_a))
    return Pool(prefix=spec.get("prefix", "pb"),
                lines=int(spec["lines_per_datagram"]),
                kind=kind_a[order], name=name_a[order], value=value_a[order],
                half_rate=half_a[order],
                names_per_kind={k: int(v["names"])
                                for k, v in spec["kinds"].items()})


@dataclass
class ForwardPool:
    """The forwarded metrics in send order, and the raw samples each timer
    digest summarises. RPC r carries metrics [rpc_start[r], rpc_start[r+1]);
    metric i's samples are s_value[s_start[i]:s_start[i+1]], sorted (none
    for a counter, whose forwarded value is m_value)."""
    prefix: str
    compression: float
    timer_scope: str
    m_kind: np.ndarray         # int8 index into KINDS
    m_name: np.ndarray         # int32 fleet name id within its kind
    m_value: np.ndarray        # int64: a counter's summed increments
    s_start: np.ndarray        # int64, one more than the metrics
    s_value: np.ndarray        # float64 latencies
    rpc_start: np.ndarray      # int64, one more than the RPCs

    @property
    def n_metrics(self) -> int:
        return len(self.m_kind)

    @property
    def n_rpcs(self) -> int:
        return len(self.rpc_start) - 1

    @functools.cached_property
    def metric_rpc(self) -> np.ndarray:
        """The RPC each metric rides in."""
        return np.repeat(np.arange(self.n_rpcs),
                         np.diff(self.rpc_start)).astype(np.int64)

    @functools.cached_property
    def timer_samples(self):
        """(fleet name, value, metric) of every raw timer sample, sorted by
        name and then value: cut by an interval's multiplicities, the
        union of a name's samples stays sorted."""
        metric = np.repeat(np.arange(self.n_metrics), np.diff(self.s_start))
        name = self.m_name[metric]
        order = np.lexsort((self.s_value, name))
        return name[order], self.s_value[order], metric[order]

    def digest(self) -> str:
        h = hashlib.sha256()
        for a in (self.m_kind, self.m_name, self.m_value,
                  self.s_start, self.s_value, self.rpc_start):
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()[:16]


def _local_names(rng, weight: np.ndarray, local: int, n_locals: int,
                 per: int) -> np.ndarray:
    """A local's names: its round-robin share of the fleet, then names
    drawn without replacement with probability ~ weight (Gumbel top-k)."""
    own = np.arange(local, len(weight), n_locals)
    rest = np.setdiff1d(np.arange(len(weight)), own, assume_unique=True)
    more = per - len(own)
    if more:
        key = np.log(weight[rest]) - np.log(-np.log(rng.random(len(rest))))
        own = np.concatenate([own, rest[np.argpartition(-key, more - 1)[:more]]])
    return np.sort(own)


def build_forward_pool(spec: dict, seed: int) -> ForwardPool:
    n_locals, bursts = int(spec["locals"]), int(spec["bursts"])
    per_rpc = int(spec["metrics_per_rpc"])
    kinds = [k for k in FORWARD_KINDS if k in spec["kinds"]]
    held = {}                          # kind -> (each local's names, weights)
    for kind in kinds:
        k, ki = spec["kinds"][kind], KINDS.index(kind)
        rng = np.random.default_rng([int(seed), 0x6677, ki])
        n, per = int(k["names"]), int(k["names_per_local"])
        weight = 1.0 / (rng.permutation(n) + 1.0) ** float(k["zipf_s"])
        names = [_local_names(rng, weight, loc, n_locals, per)
                 for loc in range(n_locals)]
        held[kind] = (names, [weight[a] / weight[a].sum() for a in names])
    # per burst, per local in the burst's order: its metrics and samples
    kind_l, name_l, value_l, slen_l, samples_l, rpc_sizes = \
        [], [], [], [], [], []
    for b in range(bursts):
        order = np.random.default_rng([int(seed), 0x6677, 100, b]).permutation(
            n_locals)
        for loc in order.tolist():
            parts = []                 # (kind index, names, value, sample runs)
            for kind in kinds:
                k, ki = spec["kinds"][kind], KINDS.index(kind)
                names, p = held[kind][0][loc], held[kind][1][loc]
                rng = np.random.default_rng([int(seed), 0x6677, ki, loc, b])
                per, m = len(names), int(k["samples_per_local"])
                at = np.concatenate([np.arange(per),
                                     rng.choice(per, size=m - per, p=p)])
                lens = np.bincount(at, minlength=per)
                if kind == "counter":
                    inc = rng.integers(1, 1000, m)
                    value = np.bincount(at, weights=inc, minlength=per)
                    parts.append((ki, names, value.astype(np.int64),
                                  np.zeros(per, np.int64), np.zeros(0)))
                else:
                    v = np.round(rng.gamma(2.0, 15.0, m) + 0.5, 3)
                    srt = np.lexsort((v, at))
                    parts.append((ki, names, np.zeros(per, np.int64), lens,
                                  v[srt]))
            # the local's metrics in a seeded order; each keeps its samples
            ki_a = np.concatenate([np.full(len(p[1]), p[0], np.int8)
                                   for p in parts])
            nm_a = np.concatenate([p[1] for p in parts])
            val_a = np.concatenate([p[2] for p in parts])
            len_a = np.concatenate([p[3] for p in parts])
            smp_a = np.concatenate([p[4] for p in parts])
            perm = np.random.default_rng(
                [int(seed), 0x6677, 200, loc, b]).permutation(len(ki_a))
            kind_l.append(ki_a[perm])
            name_l.append(nm_a[perm].astype(np.int32))
            value_l.append(val_a[perm])
            slen_l.append(len_a[perm])
            # each sample follows its metric to the metric's place in perm
            place = np.empty_like(perm)
            place[perm] = np.arange(len(perm))
            samples_l.append(smp_a[np.argsort(np.repeat(place, len_a),
                                              kind="stable")])
            full, tail = divmod(len(perm), per_rpc)
            rpc_sizes += [per_rpc] * full + ([tail] if tail else [])
    slen = np.concatenate(slen_l)
    return ForwardPool(
        prefix=spec.get("prefix", "pb"),
        compression=float(spec["compression"]),
        timer_scope=spec["kinds"].get("timer", {}).get("scope", "mixed"),
        m_kind=np.concatenate(kind_l), m_name=np.concatenate(name_l),
        m_value=np.concatenate(value_l),
        s_start=np.concatenate([[0], np.cumsum(slen)]).astype(np.int64),
        s_value=np.concatenate(samples_l),
        rpc_start=np.concatenate([[0], np.cumsum(rpc_sizes)]).astype(
            np.int64))
