"""Cross-PROCESS collective merge: two jax processes (CPU, Gloo backend)
form one (replica=2, shard=2) mesh — each process owns one replica row —
ingest disjoint sample streams, and the merged flush's psum/all-gather
collectives run across the process boundary (the DCN analogue). Rank 0
and rank 1 must both observe the identical merged totals.

Architecture note: production cross-host transport is the name-keyed
gRPC tier (parallel/multihost.py docstring); this validates that the
COLLECTIVE layer itself is multi-controller-clean for pod-slice global
tiers, where slot alignment is the caller's contract (identical
insertion order here).
"""

import os
import subprocess
import sys

import pytest

_CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
rank = int(sys.argv[1]); port = sys.argv[2]
sys.path.insert(0, os.environ["VENEUR_REPO"])
import numpy as np
import jax
from veneur_tpu.parallel.multihost import (
    init_multihost, multihost_empty_state, put_process_local_batch)
from veneur_tpu.parallel.sharded import (
    make_mesh, make_merged_flush, make_sharded_ingest, stack_batches)
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.aggregation.host import Batcher, BatchSpec

init_multihost(f"127.0.0.1:{port}", num_processes=2, process_id=rank)
assert jax.process_count() == 2 and len(jax.devices()) == 4

R, S = 2, 2
spec = TableSpec(counter_capacity=16, gauge_capacity=8, status_capacity=4,
                 set_capacity=4, histo_capacity=8, hll_precision=12)
bspec = BatchSpec(counter=32, gauge=8, status=4, set=8, histo=64)
mesh = make_mesh(R, S)
ingest = make_sharded_ingest(mesh, spec)
flush = make_merged_flush(mesh, spec)
state = multihost_empty_state(spec, R, S, mesh)

# this process's replica row: counters +(rank+1) into slot 3 of shard 0
# and slot 1 of shard 1; timers rank-distinct values into shard 1 slot 2
rows = []
for s in range(S):
    b = Batcher(spec, bspec)
    if s == 0:
        for _ in range(10):
            b.add_counter(3, float(rank + 1), 1.0)
    else:
        b.add_counter(1, 100.0 * (rank + 1), 1.0)
        for v in range(1, 11):
            b.add_histo(2, float(v + 10 * rank), 1.0)
    rows.append(b.force_emit())
local = stack_batches([rows], 1, S)        # [1, S, ...] = my replica row
batch = put_process_local_batch(local, mesh, R)
state = ingest(state, batch)

out = flush(state, np.asarray([0.5], np.float32))
from veneur_tpu.aggregation.step import finish_flush
res = finish_flush({k: np.asarray(v) for k, v in out.items()})
# merged across BOTH processes: shard 0 slot 3 = 10*1 + 10*2
assert res["counter"][0, 3] == 30.0, res["counter"][0]
# shard 1 slot 1 = 100 + 200
assert res["counter"][1, 1] == 300.0, res["counter"][1]
# merged digest: 20 samples 1..10 and 11..20 -> median ~10.5
med = float(res["histo_quantiles"][1, 2, 0])
assert abs(med - 10.5) < 1.5, med
print(f"rank{rank} MERGED OK median={med}", flush=True)
"""


def test_two_process_collective_merge(tmp_path):
    if sys.platform != "linux":
        pytest.skip("gloo cpu backend exercised on linux only")
    # pid-derived coordinator port below the ephemeral range (32768+),
    # above the registered range's busy spots (a bind-then-close
    # free-port probe would be TOCTOU-racy)
    port = str(21000 + os.getpid() % 11000)
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    env = dict(os.environ,
               VENEUR_REPO=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen([sys.executable, str(script), str(r), port],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True, env=env)
             for r in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=210)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost child timed out")
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank{r} failed:\n{out[-2000:]}"
        assert "MERGED OK" in out, out[-2000:]
