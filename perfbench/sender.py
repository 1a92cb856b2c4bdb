"""The load generator: a child process that imports neither JAX nor the
program. It builds the pool from the traffic file and the seed, then
streams its datagrams round and round over one connected UDP socket, in
batches through sendmmsg(2), credit-bounded against the engine's
processed count, which the parent publishes through a small shared
control block (an mmap of a file under the run's temporary directory).

    python perfbench/sender.py <control file> <traffic file> <seed>
    python perfbench/sender.py --ceiling <traffic file> <seed>

Control block: int64 slots, see the names below. The parent writes
PORT, CMD, SEQ, LIMIT, CREDIT and PROCESSED (and zeroes POLL_MAX_NS, the
longest single sleep of the child's wait for credit); the child writes the
rest.
POS, SENT and LAST_SEND_NS are stored once a batch, before CMD is read
again: what the parent reads at a pause's acknowledgement is what left.

`--ceiling` is a hand check, no part of a run: the same loop against a
socket of its own that a forked reader only discards from, with unbounded
credit, for a few seconds; it prints the datagrams and samples a second
the generator itself can reach on this machine (PERF.md has the readings).
"""

from __future__ import annotations

import ctypes
import errno
import mmap
import os
import socket
import sys
import time
from bisect import bisect_right

SLOTS = 16
(PORT, CMD, SEQ, LIMIT, CREDIT, PROCESSED,
 STATE, ACK, POS, SENT, LAST_SEND_NS, BLOCKED_NS, N_DATAGRAMS,
 POOL_DIGEST, POLL_MAX_NS) = range(15)
RUN, PAUSE, STOP = 0, 1, 2                      # CMD
STARTING, READY, RUNNING, PAUSED, AT_LIMIT, GONE = range(6)   # STATE

BATCH = 32          # datagrams to a sendmmsg call, at the most
RETRY = (errno.EAGAIN, errno.ENOBUFS, errno.EINTR)
CEILING_S = 3.0


class iovec(ctypes.Structure):
    _fields_ = [("iov_base", ctypes.c_void_p), ("iov_len", ctypes.c_size_t)]


class msghdr(ctypes.Structure):
    _fields_ = [("msg_name", ctypes.c_void_p),
                ("msg_namelen", ctypes.c_uint32),
                ("msg_iov", ctypes.c_void_p),
                ("msg_iovlen", ctypes.c_size_t),
                ("msg_control", ctypes.c_void_p),
                ("msg_controllen", ctypes.c_size_t),
                ("msg_flags", ctypes.c_int)]


class mmsghdr(ctypes.Structure):
    _fields_ = [("msg_hdr", msghdr), ("msg_len", ctypes.c_uint)]


def open_block(path: str, create: bool = False):
    if create:
        with open(path, "wb") as f:
            f.write(b"\0" * (8 * SLOTS))
    f = open(path, "r+b")
    mm = mmap.mmap(f.fileno(), 8 * SLOTS)
    f.close()
    return mm, memoryview(mm).cast("q")


def load_sendmmsg():
    """sendmmsg of the C library the machine has. There is no other way
    to send: where it cannot be loaded the run fails here."""
    libc = ctypes.CDLL(None, use_errno=True)
    try:
        fn = libc.sendmmsg
    except AttributeError:
        raise RuntimeError("perfbench sender: the C library has no "
                           "sendmmsg, and there is no other loop") from None
    fn.argtypes = (ctypes.c_int, ctypes.c_void_p, ctypes.c_uint, ctypes.c_int)
    fn.restype = ctypes.c_int
    return fn


class Stream:
    """The pool laid out for sendmmsg: one buffer of all its datagrams,
    one iovec and one mmsghdr a datagram over it (msg_name null: the
    socket is connected), and `before[i]`, the samples in datagrams
    [0, i)."""

    def __init__(self, dgrams: list, sizes: list):
        self.n = n = len(dgrams)
        self.buf = ctypes.create_string_buffer(b"".join(dgrams))
        self.iov = (iovec * n)()
        self.hdrs = (mmsghdr * n)()
        self.hdrs_at = ctypes.addressof(self.hdrs)
        at, iov_at = ctypes.addressof(self.buf), ctypes.addressof(self.iov)
        self.before = [0]
        for i, (d, size) in enumerate(zip(dgrams, sizes)):
            self.iov[i].iov_base, self.iov[i].iov_len = at, len(d)
            hdr = self.hdrs[i].msg_hdr
            hdr.msg_iov = iov_at + i * ctypes.sizeof(iovec)
            hdr.msg_iovlen = 1
            at += len(d)
            self.before.append(self.before[-1] + size)

    def batch(self, i: int, most: int, room: int) -> int:
        """How many of the datagrams from i on may leave in one call:
        `most` at the most, none past the pool's end, and no more samples
        than `room`. 0 (or less) where not even datagram i fits."""
        before = self.before
        hi = min(self.n, i + most)
        return bisect_right(before, before[i] + room, i, hi + 1) - 1 - i


def main(argv) -> int:
    parent = os.getppid()
    ctl_path, traffic_path, seed = argv[1], argv[2], int(argv[3])
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import traffic
    sendmmsg = load_sendmmsg()
    mm, ctl = open_block(ctl_path)
    pool = traffic.build_pool(traffic.load(traffic_path), seed)
    stream = Stream(pool.datagrams(), pool.datagram_sizes().tolist())
    n, before, hdrs_at = stream.n, stream.before, stream.hdrs_at
    hdr_size = ctypes.sizeof(mmsghdr)
    ctl[N_DATAGRAMS] = n
    ctl[POOL_DIGEST] = int(pool.digest()[:15], 16)
    ctl[STATE] = READY
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    now = time.monotonic_ns
    pos = sent = blocked = 0
    try:
        while ctl[PORT] == 0:
            if ctl[CMD] == STOP or os.getppid() != parent:
                return 0
            time.sleep(0.002)
        sock.connect(("127.0.0.1", ctl[PORT]))
        fd = sock.fileno()
        while True:
            cmd = ctl[CMD]
            if cmd == STOP or os.getppid() != parent:
                break
            left = ctl[LIMIT] - pos
            if cmd == PAUSE or left <= 0:
                ctl[BLOCKED_NS] = blocked
                if cmd == PAUSE:
                    # only a pause is acknowledged: an ack from the limit
                    # could be read as the answer to a pause not yet seen
                    ctl[ACK] = ctl[SEQ]
                ctl[STATE] = PAUSED if cmd == PAUSE else AT_LIMIT
                time.sleep(0.0002)
                continue
            i = pos % n
            k = stream.batch(i, min(BATCH, left),
                             ctl[PROCESSED] + ctl[CREDIT] - sent)
            if k <= 0:
                # the credit does not hold datagram i: wait for the engine
                t0, need = now(), before[i + 1] - before[i]
                t = t0
                while (ctl[PROCESSED] + ctl[CREDIT] - sent < need
                       and ctl[CMD] == RUN and os.getppid() == parent):
                    time.sleep(0.0001)
                    # the longest single look: where this reads long with
                    # the parent's publisher, the machine stood still
                    t, before_sleep = now(), t
                    if t - before_sleep > ctl[POLL_MAX_NS]:
                        ctl[POLL_MAX_NS] = t - before_sleep
                blocked += t - t0
                ctl[BLOCKED_NS] = blocked
                continue
            ctl[STATE] = RUNNING
            done = sendmmsg(fd, hdrs_at + i * hdr_size, k, 0)
            if done < 0:
                err = ctypes.get_errno()
                if err in RETRY:
                    continue
                raise OSError(err, "perfbench sender: sendmmsg: "
                              + os.strerror(err))
            # a short count advances by what was sent
            pos += done
            sent += before[i + done] - before[i]
            ctl[LAST_SEND_NS] = now()
            ctl[SENT] = sent
            ctl[POS] = pos
    finally:
        ctl[BLOCKED_NS] = blocked
        ctl[STATE] = GONE
        sock.close()
        ctl.release()
        mm.close()
    return 0


def ceiling(traffic_path: str, seed: int) -> dict:
    """The loop against a control block and a socket of its own: unbounded
    credit, a forked reader that only discards."""
    import tempfile
    import threading
    sink = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sink.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 2 << 20)
    sink.bind(("127.0.0.1", 0))
    reader = os.fork()
    if reader == 0:
        buf = bytearray(65536)
        while True:
            sink.recv_into(buf)
    out = {}

    def clock(ctl):
        while ctl[SENT] == 0:
            time.sleep(0.001)
        t0, d0, s0 = time.monotonic(), ctl[POS], ctl[SENT]
        time.sleep(CEILING_S)
        t1, d1, s1 = time.monotonic(), ctl[POS], ctl[SENT]
        ctl[CMD] = STOP
        out.update(seconds=t1 - t0, datagrams_per_s=(d1 - d0) / (t1 - t0),
                   samples_per_s=(s1 - s0) / (t1 - t0))

    try:
        with tempfile.TemporaryDirectory(prefix="perfbench-ceiling-") as tmp:
            path = os.path.join(tmp, "control")
            mm, ctl = open_block(path, create=True)
            ctl[PORT] = sink.getsockname()[1]
            ctl[CREDIT] = ctl[LIMIT] = 2 ** 62
            t = threading.Thread(target=clock, args=(ctl,), daemon=True)
            t.start()
            main([None, path, traffic_path, str(seed)])
            t.join()
            ctl.release()
            mm.close()
    finally:
        os.kill(reader, 9)
        os.waitpid(reader, 0)
        sink.close()
    return out


if __name__ == "__main__":
    if sys.argv[1] == "--ceiling":
        print(" ".join(f"{k}={v:.1f}" for k, v in
                       ceiling(sys.argv[2], int(sys.argv[3])).items()))
        sys.exit(0)
    sys.exit(main(sys.argv))
