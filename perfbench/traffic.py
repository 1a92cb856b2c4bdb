"""The one traffic generator: a traffic file's parameters and a seed give
a pool of DogStatsD samples, cut into datagrams and cycled by the sender.

Pure NumPy and stdlib: the sender child imports this module and nothing
of JAX or the program. The parent and the child each build the pool from
the same file and seed; `Pool.digest()` is compared so that the two can
never disagree in silence.

A traffic file (`perfbench/traffic/<mix>.json`) holds, per kind, `names`,
`samples` in the pool and the Zipf exponent `zipf_s`. The first `names`
samples of a kind cover each name once, so every interval of at least one
pool cycle touches every name and the flush's row count does not follow
the throughput; the rest are drawn Zipf(s) over a seeded permutation of
the names. Every seed gives the same sizes, another order and other draws.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass

import numpy as np

KINDS = ("counter", "gauge", "timer", "set")
LETTER = {"counter": "c", "gauge": "g", "timer": "t", "set": "s"}
WIRE = {"counter": "c", "gauge": "g", "timer": "ms", "set": "s"}


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    for kind in spec["kinds"]:
        if kind not in KINDS:
            raise ValueError(f"{path}: unknown kind {kind!r}")
        k = spec["kinds"][kind]
        if k["samples"] < k["names"]:
            raise ValueError(f"{path}: {kind} has fewer samples than names")
    return spec


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
