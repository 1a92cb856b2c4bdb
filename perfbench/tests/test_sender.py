"""The load generator alone: the child against a UDP socket the test owns
and a control block the test drives, over a tiny traffic file. No JAX, no
server; every test has a time limit of its own."""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

import sender as S
import traffic
from selfcheck import TINY

SENDER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "sender.py")
# selfcheck.py's tiny pool: 610 samples in datagrams of 7 make 88
# datagrams, the last of one sample, and 88 is no multiple of the batch
SEED = 2 ** 31 + 77
FOREVER = 2 ** 62


@pytest.fixture(autouse=True)
def time_limit():
    def over(signum, frame):
        raise TimeoutError("the test passed its time limit of 30 s")
    old = signal.signal(signal.SIGALRM, over)
    signal.alarm(30)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


class Bench:
    """The test's side: the socket, the control block and the child."""

    def __init__(self, tmp_path, start=True):
        self.traffic_path = str(tmp_path / "tiny.json")
        with open(self.traffic_path, "w") as f:
            json.dump(TINY, f)
        pool = traffic.build_pool(TINY, SEED)
        self.dgrams = pool.datagrams()
        self.sizes = pool.datagram_sizes().tolist()
        self.n = len(self.dgrams)
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4 << 20)
        self.sock.bind(("127.0.0.1", 0))
        self.ctl_path = str(tmp_path / "control")
        self.mm, self.ctl = S.open_block(self.ctl_path, create=True)
        self.got = []                 # every datagram received, in order
        self.child = None
        if start:
            self.child = subprocess.Popen(self.command())
            self.wait(lambda: self.ctl[S.STATE] >= S.READY, "the pool")

    def command(self):
        return [sys.executable, SENDER, self.ctl_path, self.traffic_path,
                str(SEED)]

    def go(self, limit, credit):
        self.ctl[S.CREDIT], self.ctl[S.LIMIT] = credit, limit
        self.ctl[S.PORT] = self.sock.getsockname()[1]

    def wait(self, cond, what, timeout=10.0):
        end = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < end, f"timed out waiting for {what}"
            time.sleep(0.001)

    def samples(self, n_datagrams):
        """Samples in the first n datagrams of the cycled stream."""
        whole, rest = divmod(n_datagrams, self.n)
        return whole * sum(self.sizes) + sum(self.sizes[:rest])

    def receive(self, quiet=0.05):
        """Everything that arrives until the socket has been quiet for
        `quiet` seconds."""
        self.sock.settimeout(quiet)
        try:
            while True:
                self.got.append(self.sock.recv(65536))
        except socket.timeout:
            pass

    def in_order(self):
        return all(d == self.dgrams[i % self.n]
                   for i, d in enumerate(self.got))

    def close(self):
        self.ctl[S.CMD] = S.STOP
        if self.child is not None:
            try:
                self.child.wait(5)
            except subprocess.TimeoutExpired:
                self.child.kill()
                self.child.wait()
        self.sock.close()
        self.ctl.release()
        self.mm.close()


@pytest.fixture()
def bench(tmp_path):
    b = Bench(tmp_path)
    yield b
    b.close()


def test_the_layout_sendmmsg_reads():
    """The structures are the C library's: a header is 64 bytes on a
    64-bit machine, and every header points at its own datagram."""
    import ctypes
    pool = traffic.build_pool(TINY, SEED)
    stream = S.Stream(pool.datagrams(), pool.datagram_sizes().tolist())
    assert ctypes.sizeof(S.iovec) == 2 * ctypes.sizeof(ctypes.c_void_p)
    if ctypes.sizeof(ctypes.c_void_p) == 8:
        assert ctypes.sizeof(S.msghdr) == 56 and ctypes.sizeof(S.mmsghdr) == 64
    for i, d in enumerate(pool.datagrams()):
        iov = S.iovec.from_address(stream.hdrs[i].msg_hdr.msg_iov)
        assert ctypes.string_at(iov.iov_base, iov.iov_len) == d
        assert stream.hdrs[i].msg_hdr.msg_name is None
    assert stream.before[-1] == pool.n_samples
    # a batch: the fixed size, the pool's end, the limit and the credit
    assert stream.batch(0, 32, FOREVER) == 32
    assert stream.batch(80, 32, FOREVER) == 8
    assert stream.batch(0, 5, FOREVER) == 5
    assert stream.batch(0, 32, 20) == 2 and stream.batch(0, 32, 21) == 3
    assert stream.batch(0, 32, 6) <= 0 and stream.batch(3, 32, -50) <= 0
    assert stream.batch(87, 32, 1) == 1       # the short last datagram


@pytest.mark.parametrize("limit, credit_datagrams", [
    (2 * 88 + 5, None),     # the pool's length is no multiple of the batch
    (40, None),             # the limit falls inside the second batch
    (88 + 3, 3),            # the credit is smaller than one batch
    (150, 40),              # the credit is a batch and a part
], ids=["pool-wraps", "limit-in-batch", "credit-under-batch",
        "credit-over-batch"])
def test_stream_is_the_pool_cycled_and_stops_at_the_limit(
        bench, limit, credit_datagrams):
    ctl = bench.ctl
    credit = FOREVER if credit_datagrams is None else 7 * credit_datagrams
    bench.go(limit, credit)
    ahead_most = 0
    while len(bench.got) < limit:
        bench.receive(quiet=0.02)
        # the engine's part: what has been received has been processed
        ahead_most = max(ahead_most, ctl[S.SENT] - ctl[S.PROCESSED])
        ctl[S.PROCESSED] = bench.samples(len(bench.got))
    bench.wait(lambda: ctl[S.STATE] == S.AT_LIMIT, "the limit")
    bench.receive(quiet=0.2)                  # nothing more may come
    assert len(bench.got) == limit == ctl[S.POS]
    assert bench.in_order()
    assert ctl[S.SENT] == bench.samples(limit)
    assert ahead_most <= credit
    if credit_datagrams is not None:
        assert ahead_most > 0 and ctl[S.BLOCKED_NS] > 0


def test_the_sender_is_never_further_ahead_than_the_credit(bench):
    """The reader is held: with nothing processed, exactly the credit's
    worth of whole datagrams leaves, and not one more."""
    ctl = bench.ctl
    bench.go(FOREVER, 7 * 10 + 3)             # ten datagrams and a part
    bench.receive(quiet=0.3)
    assert len(bench.got) == 10 and ctl[S.SENT] == 70
    ctl[S.PROCESSED] = 7 * 4                  # four more fit: 28 + 73 = 101
    bench.receive(quiet=0.3)
    assert len(bench.got) == 14 and ctl[S.SENT] == 98 and bench.in_order()


def test_a_pause_is_acknowledged_with_what_left(bench):
    ctl = bench.ctl
    bench.go(FOREVER, 7 * 50)
    while len(bench.got) < 300:
        bench.receive(quiet=0.005)
        ctl[S.PROCESSED] = bench.samples(len(bench.got))
    ctl[S.SEQ] += 1
    ctl[S.CMD] = S.PAUSE
    bench.wait(lambda: ctl[S.ACK] == ctl[S.SEQ], "the acknowledgement")
    pos, sent, t_last = ctl[S.POS], ctl[S.SENT], ctl[S.LAST_SEND_NS]
    bench.receive(quiet=0.2)
    assert len(bench.got) == pos and bench.samples(pos) == sent
    assert bench.in_order()
    assert 0 < time.monotonic_ns() - t_last < 5e9
    ctl[S.PROCESSED] = sent                   # credit, but no leave to send
    time.sleep(0.1)
    bench.receive(quiet=0.1)
    assert (ctl[S.POS], ctl[S.SENT], ctl[S.LAST_SEND_NS]) == (pos, sent, t_last)
    assert len(bench.got) == pos and ctl[S.STATE] == S.PAUSED
    ctl[S.CMD] = S.RUN
    bench.wait(lambda: ctl[S.POS] > pos, "the stream to go on")
    bench.receive(quiet=0.1)
    assert bench.in_order()


def test_blocked_time_grows_only_while_credit_is_withheld(bench):
    ctl = bench.ctl

    def send_until(limit):
        ctl[S.LIMIT] = limit
        bench.wait(lambda: ctl[S.STATE] == S.AT_LIMIT
                   and ctl[S.POS] == limit, f"datagram {limit}")
        return ctl[S.BLOCKED_NS]

    bench.go(0, FOREVER)
    assert send_until(50) == 0                # ample credit: never blocked
    ctl[S.CREDIT] = ctl[S.SENT]               # nothing processed, no room
    ctl[S.LIMIT] = 100
    time.sleep(0.25)
    assert ctl[S.POS] == 50
    ctl[S.CREDIT] = FOREVER
    held = send_until(100)
    assert 0.2e9 <= held <= 2e9
    assert send_until(150) == held            # ample credit again


def test_stop_ends_the_child(bench):
    bench.go(FOREVER, FOREVER)
    bench.wait(lambda: bench.ctl[S.POS] > 0, "the first batch")
    bench.ctl[S.CMD] = S.STOP
    assert bench.child.wait(5) == 0
    assert bench.ctl[S.STATE] == S.GONE


STARTER = """
import struct, subprocess, sys, time
subprocess.Popen(sys.argv[2:])
while struct.unpack_from("q", open(sys.argv[1], "rb").read(), 8 * %d)[0] < %d:
    time.sleep(0.01)
""" % (S.STATE, S.READY)


@pytest.mark.parametrize("streaming", [False, True],
                         ids=["waiting-for-the-port", "streaming"])
def test_a_dead_parent_ends_the_child(tmp_path, streaming):
    """The child is started by a process that exits once the pool is
    built: before the port is known, and with the stream running."""
    bench = Bench(tmp_path, start=False)
    try:
        if streaming:
            bench.go(FOREVER, FOREVER)
        starter = subprocess.run([sys.executable, "-c", STARTER,
                                  bench.ctl_path] + bench.command())
        assert starter.returncode == 0
        bench.wait(lambda: bench.ctl[S.STATE] == S.GONE, "the child's end")
        if streaming:
            bench.receive(quiet=0.2)
            assert bench.got and bench.in_order()
    finally:
        bench.close()
