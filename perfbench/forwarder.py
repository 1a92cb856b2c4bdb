"""The forward load generator: a child process that imports neither JAX
nor the program, but for the generated wire-schema modules
`veneur_tpu/proto/*_pb2` (imported by the tests, to decode what this
writes). It builds the forward pool from the traffic file and the seed
(`traffic.build_forward_pool`), makes each timer's t-digest from its raw
samples, encodes every RPC once, then sends them round and round to
`forwardrpc.Forward/SendMetrics` on the global's gRPC port, one RPC at a
time, credit-bounded against the server's `imported_total`, which the
parent publishes through the control block of `sender.py` (same slots).

    python perfbench/forwarder.py <control file> <traffic file> <seed>
    python perfbench/forwarder.py --ceiling <traffic file> <seed>

In the control block POS counts RPCs, SENT metrics, N_DATAGRAMS is the
pool's RPCs and LAST_SEND_NS the moment the last RPC was acknowledged.
CREDIT caps the metrics sent beyond PROCESSED. A pause is acknowledged
between two RPCs, so what the parent reads then has all been acknowledged.

`--ceiling` is a hand check, no part of a run: the same loop against a
gRPC server of its own (another process) that acknowledges and does
nothing else, with unbounded credit, for a few seconds; it prints the
RPCs, metrics and megabytes a second the generator itself reaches on this
machine (PERF.md has the readings).

The digest: upstream `tdigest/merging_digest.go`'s merge (`mergeOne`,
`indexEstimate`) over a timer's samples in sorted order, each of weight
one: sample j of n joins the open centroid while
k((j + 1) / n) - k(start / n) <= 1, k(q) = compression * (asin(2q - 1) /
pi + 1/2) (the k1 scale), and opens the next one otherwise. Weights are
sample counts, so a digest's total weight is its samples exactly; min, max
and the reciprocal sum are the samples' own.
"""

from __future__ import annotations

import os
import struct
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import sender as S      # noqa: E402  the control block's slots
import traffic          # noqa: E402

METHOD = "/forwardrpc.Forward/SendMetrics"
CEILING_S = 3.0
RPC_TIMEOUT_S = 60.0
TYPE_TIMER, SCOPE_GLOBAL = 4, 2       # metricpb.Type.Timer, metricpb.Scope.Global


def k1(q, compression: float):
    return compression * (np.arcsin(2.0 * q - 1.0) / np.pi + 0.5)


def merge_boundaries(values: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                     compression: float) -> np.ndarray:
    """For runs of sorted samples (run i is values[starts[i]:starts[i] +
    lens[i]]), whether each sample opens a centroid of its run's digest.
    All runs advance in step, sample j of every run at once: the longest
    run sets the number of steps, the samples the work."""
    opens = np.zeros(len(values), bool)
    if not len(lens):
        return opens
    order = np.argsort(-lens, kind="stable")
    n_desc, at = lens[order], starts[order]
    n = n_desc.astype(np.float64)
    before = np.zeros(len(order))      # k of the open centroid's start
    opens[at] = True
    for j in range(1, int(n_desc[0])):
        a = int(np.searchsorted(-n_desc, -j, "left"))   # runs longer than j
        nj = n[:a]
        new = np.flatnonzero(k1((j + 1) / nj, compression) - before[:a] > 1)
        opens[at[new] + j] = True
        before[new] = k1(j / nj[new], compression)
    return opens


class Digests:
    """Every timer metric's digest, as columns. Metric i's centroids are
    mean[c_start[i]:c_start[i + 1]] and weight[...] (none for a counter)."""

    def __init__(self, pool: traffic.ForwardPool):
        lens = np.diff(pool.s_start)
        timer = np.flatnonzero(lens > 0)
        opens = merge_boundaries(pool.s_value, pool.s_start[timer],
                                 lens[timer], pool.compression)
        cid = np.cumsum(opens) - 1
        self.weight = np.bincount(cid).astype(np.float64)
        self.mean = np.bincount(cid, weights=pool.s_value) / self.weight
        self.c_start = np.concatenate([[0], np.cumsum(opens)])[pool.s_start]
        first, last = pool.s_start[timer], pool.s_start[timer] + lens[timer] - 1
        self.min = np.zeros(pool.n_metrics)
        self.max = np.zeros(pool.n_metrics)
        self.recip = np.zeros(pool.n_metrics)
        self.min[timer] = pool.s_value[first]
        self.max[timer] = pool.s_value[last]
        self.recip[timer] = np.add.reduceat(1.0 / pool.s_value, first)


# -- protobuf wire encoding (the schema: veneur_tpu/proto/*.proto) ------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _field(number: int, payload: bytes) -> bytes:
    """A length-delimited field."""
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


# a centroid, tdigest.Centroid{mean = 1, weight = 2}, as main_centroids = 1
_CENTROID = np.dtype([("tag", "u1"), ("len", "u1"), ("mean_tag", "u1"),
                      ("mean", "<f8"), ("weight_tag", "u1"),
                      ("weight", "<f8")])


def encode(pool: traffic.ForwardPool, dg: Digests):
    """Every RPC's serialized forwardrpc.MetricList, and its metrics.
    Fields at their proto3 defaults are left out, as protobuf does: a
    counter's type (Counter = 0), a mixed timer's scope (Mixed = 0)."""
    cent = np.zeros(len(dg.mean), _CENTROID)
    cent["tag"], cent["len"] = 0x0A, 18
    cent["mean_tag"], cent["weight_tag"] = 0x09, 0x11
    cent["mean"], cent["weight"] = dg.mean, dg.weight
    cent = cent.tobytes()
    width = _CENTROID.itemsize
    p, comp = pool.prefix, struct.pack("<d", pool.compression)
    scope = b"\x48" + _varint(SCOPE_GLOBAL)
    timer_tail = scope if pool.timer_scope == "global" else b""
    counter = traffic.KINDS.index("counter")
    metrics = []
    for i, (kind, name) in enumerate(zip(pool.m_kind.tolist(),
                                         pool.m_name.tolist())):
        if kind == counter:
            body = (_field(1, f"{p}.c.{name:07d}".encode())
                    + _field(2, f"k:{name % 8}".encode())
                    + _field(5, b"\x08" + _varint(int(pool.m_value[i])))
                    + scope)
        else:
            td = (cent[dg.c_start[i] * width:dg.c_start[i + 1] * width]
                  + b"\x11" + comp
                  + struct.pack("<BdBdBd", 0x19, dg.min[i], 0x21, dg.max[i],
                                0x29, dg.recip[i]))
            body = (_field(1, f"{p}.t.{name:07d}".encode())
                    + b"\x18" + _varint(TYPE_TIMER)
                    + _field(7, _field(1, td)) + timer_tail)
        metrics.append(_field(1, body))
    starts = pool.rpc_start.tolist()
    rpcs = [b"".join(metrics[a:b]) for a, b in zip(starts[:-1], starts[1:])]
    return rpcs, np.diff(pool.rpc_start).tolist()


def load_stream(traffic_path: str, seed: int):
    pool = traffic.build_forward_pool(traffic.load(traffic_path), seed)
    rpcs, sizes = encode(pool, Digests(pool))
    return pool, rpcs, sizes


# -- the loop -----------------------------------------------------------------

def stream(ctl, rpcs: list, sizes: list, parent: int) -> None:
    """Send until STOP (or the parent is gone), as sender.py streams."""
    import grpc
    now = time.monotonic_ns
    pos = sent = blocked = 0
    while ctl[S.PORT] == 0:
        if ctl[S.CMD] == S.STOP or os.getppid() != parent:
            return
        time.sleep(0.002)
    channel = grpc.insecure_channel(f"127.0.0.1:{ctl[S.PORT]}")
    try:
        send = channel.unary_unary(METHOD)      # bytes in, bytes out
        n = len(rpcs)
        while True:
            cmd = ctl[S.CMD]
            if cmd == S.STOP or os.getppid() != parent:
                break
            if cmd == S.PAUSE or ctl[S.LIMIT] - pos <= 0:
                ctl[S.BLOCKED_NS] = blocked
                if cmd == S.PAUSE:
                    ctl[S.ACK] = ctl[S.SEQ]
                ctl[S.STATE] = S.PAUSED if cmd == S.PAUSE else S.AT_LIMIT
                time.sleep(0.0002)
                continue
            i = pos % n
            need = sizes[i]
            if ctl[S.PROCESSED] + ctl[S.CREDIT] - sent < need:
                t0 = t = now()
                while (ctl[S.PROCESSED] + ctl[S.CREDIT] - sent < need
                       and ctl[S.CMD] == S.RUN and os.getppid() == parent):
                    time.sleep(0.0001)
                    t, before_sleep = now(), t
                    if t - before_sleep > ctl[S.POLL_MAX_NS]:
                        ctl[S.POLL_MAX_NS] = t - before_sleep
                blocked += t - t0
                ctl[S.BLOCKED_NS] = blocked
                continue
            ctl[S.STATE] = S.RUNNING
            send(rpcs[i], timeout=RPC_TIMEOUT_S)        # returns on the ack
            pos += 1
            sent += need
            ctl[S.LAST_SEND_NS] = now()
            ctl[S.SENT] = sent
            ctl[S.POS] = pos
    finally:
        ctl[S.BLOCKED_NS] = blocked
        channel.close()


def main(argv) -> int:
    parent = os.getppid()
    ctl_path, traffic_path, seed = argv[1], argv[2], int(argv[3])
    mm, ctl = S.open_block(ctl_path)
    try:
        pool, rpcs, sizes = load_stream(traffic_path, seed)
        ctl[S.N_DATAGRAMS] = len(rpcs)
        ctl[S.POOL_DIGEST] = int(pool.digest()[:15], 16)
        ctl[S.STATE] = S.READY
        stream(ctl, rpcs, sizes, parent)
    finally:
        ctl[S.STATE] = S.GONE
        ctl.release()
        mm.close()
    return 0


# -- the ceiling --------------------------------------------------------------

def _ack_server(conn) -> None:
    """A Forward service that acknowledges every RPC and keeps nothing,
    four handler threads as `forward/rpc.serve` has."""
    from concurrent import futures

    import grpc
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers((grpc.method_handlers_generic_handler(
        "forwardrpc.Forward",
        {"SendMetrics": grpc.unary_unary_rpc_method_handler(
            lambda request, context: b"")}),))
    conn.send(server.add_insecure_port("127.0.0.1:0"))
    server.start()
    conn.recv()                        # until the parent is done
    server.stop(0)


def ceiling(traffic_path: str, seed: int) -> dict:
    import multiprocessing
    import tempfile
    import threading
    _pool, rpcs, sizes = load_stream(traffic_path, seed)
    nbytes = np.concatenate([[0], np.cumsum([len(r) for r in rpcs])])
    parent_end, child_end = multiprocessing.Pipe()
    acker = multiprocessing.get_context("spawn").Process(
        target=_ack_server, args=(child_end,), daemon=True)
    acker.start()
    out = {}

    def bytes_at(pos):
        cycles, i = divmod(pos, len(rpcs))
        return cycles * int(nbytes[-1]) + int(nbytes[i])

    def clock(ctl):
        while ctl[S.SENT] == 0:
            time.sleep(0.001)
        t0, p0, s0 = time.monotonic(), ctl[S.POS], ctl[S.SENT]
        time.sleep(CEILING_S)
        t1, p1, s1 = time.monotonic(), ctl[S.POS], ctl[S.SENT]
        ctl[S.CMD] = S.STOP
        dt = t1 - t0
        out.update(seconds=dt, rpcs_per_s=(p1 - p0) / dt,
                   metrics_per_s=(s1 - s0) / dt,
                   mb_per_s=(bytes_at(p1) - bytes_at(p0)) / dt / 1e6)

    try:
        port = parent_end.recv()
        with tempfile.TemporaryDirectory(prefix="perfbench-ceiling-") as tmp:
            mm, ctl = S.open_block(os.path.join(tmp, "control"), create=True)
            ctl[S.CREDIT] = ctl[S.LIMIT] = 2 ** 62
            ctl[S.PORT] = port
            t = threading.Thread(target=clock, args=(ctl,), daemon=True)
            t.start()
            stream(ctl, rpcs, sizes, os.getppid())
            t.join()
            ctl.release()
            mm.close()
    finally:
        parent_end.send(None)
        acker.join(10)
        if acker.is_alive():
            acker.kill()
            acker.join()
    return out


if __name__ == "__main__":
    if sys.argv[1] == "--ceiling":
        print(" ".join(f"{k}={v:.1f}" for k, v in
                       ceiling(sys.argv[2], int(sys.argv[3])).items()))
        sys.exit(0)
    sys.exit(main(sys.argv))
