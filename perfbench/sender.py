"""The load generator: a child process that imports neither JAX nor the
program. It builds the pool from the traffic file and the seed, then
streams its datagrams round and round over UDP, credit-bounded against
the engine's processed count, which the parent publishes through a small
shared control block (an mmap of a file under the run's temporary
directory).

    python perfbench/sender.py <control file> <traffic file> <seed>

Control block: int64 slots, see the names below. The parent writes
PORT, CMD, SEQ, LIMIT, CREDIT and PROCESSED; the child writes the rest.
"""

from __future__ import annotations

import mmap
import os
import socket
import sys
import time

SLOTS = 16
(PORT, CMD, SEQ, LIMIT, CREDIT, PROCESSED,
 STATE, ACK, POS, SENT, LAST_SEND_NS, BLOCKED_NS, N_DATAGRAMS,
 POOL_DIGEST) = range(14)
RUN, PAUSE, STOP = 0, 1, 2                      # CMD
STARTING, READY, RUNNING, PAUSED, AT_LIMIT, GONE = range(6)   # STATE


def open_block(path: str, create: bool = False):
    if create:
        with open(path, "wb") as f:
            f.write(b"\0" * (8 * SLOTS))
    f = open(path, "r+b")
    mm = mmap.mmap(f.fileno(), 8 * SLOTS)
    f.close()
    return mm, memoryview(mm).cast("q")


def main(argv) -> int:
    ctl_path, traffic_path, seed = argv[1], argv[2], int(argv[3])
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import traffic
    mm, ctl = open_block(ctl_path)
    pool = traffic.build_pool(traffic.load(traffic_path), seed)
    dgrams = pool.datagrams()
    sizes = pool.datagram_sizes().tolist()
    n = len(dgrams)
    ctl[N_DATAGRAMS] = n
    ctl[POOL_DIGEST] = int(pool.digest()[:15], 16)
    ctl[STATE] = READY
    while ctl[PORT] == 0:
        if ctl[CMD] == STOP or os.getppid() == 1:
            return 0
        time.sleep(0.002)
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.connect(("127.0.0.1", ctl[PORT]))
    send = sock.send
    now = time.monotonic_ns
    pos = sent = blocked = 0
    widest = max(sizes)
    try:
        while True:
            cmd = ctl[CMD]
            if cmd == STOP or os.getppid() == 1:
                break
            at_limit = pos >= ctl[LIMIT]
            if cmd == PAUSE or at_limit:
                ctl[BLOCKED_NS] = blocked
                if cmd == PAUSE:
                    # only a pause is acknowledged: an ack from the limit
                    # could be read as the answer to a pause not yet seen
                    ctl[ACK] = ctl[SEQ]
                ctl[STATE] = PAUSED if cmd == PAUSE else AT_LIMIT
                time.sleep(0.0002)
                continue
            if sent - ctl[PROCESSED] > ctl[CREDIT] - widest:
                t0 = now()
                while (sent - ctl[PROCESSED] > ctl[CREDIT] - widest
                       and ctl[CMD] == RUN and os.getppid() != 1):
                    time.sleep(0.0001)
                blocked += now() - t0
                continue
            ctl[STATE] = RUNNING
            i = pos % n
            send(dgrams[i])
            sent += sizes[i]
            pos += 1
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


if __name__ == "__main__":
    sys.exit(main(sys.argv))
