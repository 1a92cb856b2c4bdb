"""The benchmark harness's pure helpers. A run can be zeroed by harness
behavior as well as by pipeline behavior, so the pieces that decide what
survives a killed child — last-JSON-line parsing, phase scraping, budget
dominance, compile-cache placement — get pinned here like any other
component."""

import json

from benchmarks import e2e


def test_parse_last_json_line_takes_last_complete():
    out = "\n".join([
        "noise",
        json.dumps({"stage": 1}),
        json.dumps({"stage": 2, "more": True}),
    ])
    assert e2e.parse_last_json_line(out) == {"stage": 2, "more": True}


def test_parse_last_json_line_skips_truncated_tail():
    """A child killed mid-print leaves a truncated final line; the
    checkpoint line above it must win (the r03 partial-artifact
    contract)."""
    out = json.dumps({"ok": 1}) + "\n" + '{"ok": 2, "trunc'
    assert e2e.parse_last_json_line(out) == {"ok": 1}


def test_parse_last_json_line_none_on_garbage():
    assert e2e.parse_last_json_line("") is None
    assert e2e.parse_last_json_line("no json here\nat all") is None


def test_last_phase_reads_str_bytes_and_none():
    err = "BENCHPHASE warm\nnoise\nBENCHPHASE timed_loop:40/100\n"
    assert e2e.last_phase(err) == "timed_loop:40/100"
    assert e2e.last_phase(err.encode()) == "timed_loop:40/100"
    assert e2e.last_phase(None) == "none"
    assert e2e.last_phase("no markers") == "none"


def test_config_budget_dominates_child_waits():
    """Config 6's parent budget must exceed the sum of its child's
    absolute sanctioned waits regardless of E2E_CONFIG_TIMEOUT — the
    parent killing a child inside a sanctioned slow flush is exactly
    the failure the budget exists to prevent."""
    child_waits = (e2e.INIT_TIMEOUT + 3 * e2e.WARM_TIMEOUT + 300.0
                   + 4 * e2e.DRAIN_TIMEOUT)
    assert e2e._config_budget(6) > child_waits
    for n in (1, 2, 3, 4, 5):
        assert e2e._config_budget(n) == e2e.SUBPROC_TIMEOUT


def test_env_num_falls_back_on_garbage():
    """A numeric env typo must never crash the bench orchestrator into a
    zeroed artifact (r05 review finding)."""
    import os
    import bench
    os.environ["BENCH_STEPS_TESTKEY"] = "two"
    try:
        assert bench._env_num(int, "BENCH_STEPS_TESTKEY", 2) == 2
        assert bench._env_num(float, "BENCH_NO_SUCH_KEY", 1.5) == 1.5
        os.environ["BENCH_STEPS_TESTKEY"] = "3"
        assert bench._env_num(int, "BENCH_STEPS_TESTKEY", 2) == 3
    finally:
        del os.environ["BENCH_STEPS_TESTKEY"]


def test_crash_handler_reprints_banked_artifact():
    """Under the last-JSON-line-wins contract, an orchestrator crash
    AFTER a real checkpoint must re-print the banked artifact (with the
    error attached), not a zero line that erases completed stages."""
    import subprocess
    import sys
    code = (
        "import bench, json\n"
        "bench._LAST_ARTIFACT.update({'value': 42, 'platform': 'tpu'})\n"
        "art = dict(bench._LAST_ARTIFACT) or {'value': 0}\n"
        "art['orchestrator_error'] = 'RuntimeError: boom'\n"
        "print(json.dumps(art))\n"
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd="/root/repo")
    row = json.loads(p.stdout.strip())
    assert row["value"] == 42
    assert "orchestrator_error" in row


def test_e2e_main_deadline_skips_configs():
    """A deadline in the past must skip every config with an explicit
    marker instead of starting work it can't finish."""
    import time
    res = e2e.main(configs=[2, 1], scale=0.01,
                   deadline=time.monotonic() - 1.0)
    assert [r["config"] for r in res] == [2, 1]
    assert all(r.get("skipped") == "bench wall-clock guard" for r in res)


def test_cache_env_leaves_an_outside_cache_dir_alone(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set, the outside placed the
    cache: children inherit exactly that value, and no code path sets
    another directory (neither in the child env nor in this process's
    JAX config)."""
    import jax

    from veneur_tpu.utils import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/outside/cache")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    env = e2e.cache_env()
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/outside/cache"
    assert "JAX_PLATFORMS" not in env     # a device child keeps the chip
    cpu = e2e.cache_env(force_cpu=True)
    assert cpu["JAX_COMPILATION_CACHE_DIR"] == "/outside/cache"
    assert cpu["JAX_PLATFORMS"] == "cpu"  # a host-only child stays off it
    assert compile_cache.configure() == "/outside/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_env_unset_gives_checkout_xla_cache(monkeypatch):
    """Unset, every entry point lands on <checkout>/.xla_cache — a fixed
    path (the directory is part of a cache entry's key), never a temp
    name, a pid or a time. A parent env requesting cpu is inherited."""
    import os

    from veneur_tpu.utils import compile_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".xla_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compile_cache.default_dir() == want
    env = e2e.cache_env()
    assert env["JAX_COMPILATION_CACHE_DIR"] == want
    assert env["JAX_PLATFORMS"] == "cpu"
    child = {}
    assert compile_cache.configure(child) == want
    assert child == {"JAX_COMPILATION_CACHE_DIR": want}


def test_no_child_process_probes_a_kernel():
    """Kernel selection is the backend plus a module constant. A Python
    child that imports a veneur_tpu.ops module to try a kernel would need
    the chip its parent already holds — the pattern must not return:
    nothing under veneur_tpu/ both starts a subprocess and names an ops
    module in a code string, and the ops modules start no process."""
    import os
    import re
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spawn = re.compile(r"\bsubprocess\b|\bPopen\b|os\.system|multiprocessing")
    ops_in_string = re.compile(
        r"""["'][^"'\n]*(?:from|import)\s+veneur_tpu\.ops\b""")
    offenders = []
    for root, _dirs, files in os.walk(os.path.join(repo, "veneur_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                src = f.read()
            in_ops = os.path.basename(root) == "ops"
            if spawn.search(src) and (in_ops or ops_in_string.search(src)):
                offenders.append(os.path.relpath(path, repo))
    assert offenders == []
