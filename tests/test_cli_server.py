"""The operator surface as real processes: `python -m
veneur_tpu.cli.server -f config.yaml` + `cli.emit`, end to end through
the flush ticker and the localfile plugin — the reference's
cmd/veneur/main.go usage (README Quickstart). Everything else tests the
Server class in-process; this is the one place the actual daemon
entrypoint, YAML file, ticker, signal handling, and emit binary
compose."""

import os
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port(kind=socket.SOCK_DGRAM) -> int:
    s = socket.socket(socket.AF_INET, kind)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def free_udp_port() -> int:
    return free_port(socket.SOCK_DGRAM)


def cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return env


def write_config(tmp_path, port, interval="2s"):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        f'interval: "{interval}"\n'
        f'statsd_listen_addresses: ["udp://127.0.0.1:{port}"]\n'
        'percentiles: [0.5]\n'
        'aggregates: ["count"]\n'
        f'flush_file: "{tmp_path}/out.tsv"\n')
    return str(cfg)


def test_validate_config_modes(tmp_path):
    cfg = write_config(tmp_path, 8126)
    ok = subprocess.run(
        [sys.executable, "-m", "veneur_tpu.cli.server", "-f", cfg,
         "-validate-config"], capture_output=True, text=True,
        env=cpu_env(), timeout=120)
    assert ok.returncode == 0 and "config valid" in ok.stdout

    bad = tmp_path / "bad.yaml"
    bad.write_text('interval: "10s"\nnot_a_real_key: 1\n')
    strict = subprocess.run(
        [sys.executable, "-m", "veneur_tpu.cli.server", "-f", str(bad),
         "-validate-config-strict"], capture_output=True, text=True,
        env=cpu_env(), timeout=120)
    assert strict.returncode == 1
    assert "not_a_real_key" in strict.stderr


def test_daemon_emit_ticker_flush_and_graceful_exit(tmp_path):
    port = free_udp_port()
    cfg = write_config(tmp_path, port)
    env = cpu_env()
    # daemon output to a FILE, not a pipe: an undrained 64KB pipe buffer
    # would block the daemon's logging (2s-interval flush lines add up)
    # and wedge the test on daemon behavior unrelated to the assertion
    log_path = tmp_path / "daemon.log"
    log_f = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "veneur_tpu.cli.server", "-f", cfg],
        stdout=log_f, stderr=subprocess.STDOUT, text=True, env=env)
    tsv = tmp_path / "out.tsv"
    try:
        # keep emitting until the 2s ticker lands our metric in the TSV
        # (daemon startup pays the first JAX compiles on this 1-core
        # host, so the loop tolerates minutes of warm-up)
        deadline = time.time() + 300
        while time.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"daemon exited early rc={proc.returncode}:\n"
                    f"{log_path.read_text()[-2000:]}")
            rc = subprocess.run(
                [sys.executable, "-m", "veneur_tpu.cli.emit",
                 "-hostport", f"udp://127.0.0.1:{port}",
                 "-name", "cli.e2e", "-count", "7",
                 "-tag", "src:clitest"],
                capture_output=True, env=env, timeout=60).returncode
            assert rc == 0, "emit CLI failed"
            if tsv.exists() and "cli.e2e" in tsv.read_text():
                break
            time.sleep(2)
        body = tsv.read_text() if tsv.exists() else ""
        assert "cli.e2e" in body, "ticker never flushed the emitted metric"
        row = next(ln for ln in body.splitlines() if "cli.e2e" in ln)
        assert "src:clitest" in row
        # SIGTERM = drain and exit 0 (reference graceful semantics)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        log_f.close()


def free_tcp_port() -> int:
    return free_port(socket.SOCK_STREAM)


def test_proxy_daemon_routes_between_real_processes(tmp_path):
    """The full three-binary composition as actual processes: a global
    server daemon, the veneur-proxy daemon (static destination), and a
    local server daemon forwarding through the proxy — the reference's
    deployment shape (cmd/veneur-proxy/main.go), with SIGTERM draining
    each to exit 0."""
    env = cpu_env()
    procs = []

    def daemon(mod, cfg_path, name):
        log_path = tmp_path / f"{name}.log"
        f = open(log_path, "w")
        p = subprocess.Popen(
            [sys.executable, "-m", mod, "-f", str(cfg_path)],
            stdout=f, stderr=subprocess.STDOUT, text=True, env=env)
        procs.append((p, f, log_path, name))
        return p

    gport = free_tcp_port()
    gcfg = tmp_path / "global.yaml"
    gcfg.write_text(
        'interval: "2s"\n'
        'statsd_listen_addresses: []\n'
        f'grpc_address: "127.0.0.1:{gport}"\n'
        'percentiles: [0.5]\naggregates: ["count"]\n'
        f'flush_file: "{tmp_path}/global.tsv"\n')
    pport = free_tcp_port()
    pcfg = tmp_path / "proxy.yaml"
    pcfg.write_text(
        f'grpc_address: "127.0.0.1:{pport}"\n'
        f'grpc_forward_address: "127.0.0.1:{gport}"\n')
    lport = free_udp_port()
    lcfg = tmp_path / "local.yaml"
    lcfg.write_text(
        'interval: "2s"\n'
        f'statsd_listen_addresses: ["udp://127.0.0.1:{lport}"]\n'
        f'forward_address: "127.0.0.1:{pport}"\n'
        'percentiles: [0.5]\naggregates: ["count"]\n'
        f'flush_file: "{tmp_path}/local.tsv"\n')

    daemon("veneur_tpu.cli.server", gcfg, "global")
    daemon("veneur_tpu.cli.proxy", pcfg, "proxy")
    daemon("veneur_tpu.cli.server", lcfg, "local")
    gtsv = tmp_path / "global.tsv"
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            for p, _f, log_path, name in procs:
                if p.poll() is not None:
                    raise AssertionError(
                        f"{name} daemon exited rc={p.returncode}:\n"
                        f"{log_path.read_text()[-2000:]}")
            emit = subprocess.run(
                [sys.executable, "-m", "veneur_tpu.cli.emit",
                 "-hostport", f"udp://127.0.0.1:{lport}",
                 "-name", "proxied.e2e", "-count", "9",
                 "-tag", "veneurglobalonly:true"],
                capture_output=True, env=env, timeout=60)
            assert emit.returncode == 0, emit.stderr[-400:]
            if gtsv.exists() and "proxied.e2e" in gtsv.read_text():
                break
            time.sleep(2)
        assert gtsv.exists() and "proxied.e2e" in gtsv.read_text(), (
            "metric never reached the global through the proxy; logs:\n"
            + "\n".join(f"== {n}:\n{lp.read_text()[-800:]}"
                        for _p, _f, lp, n in procs))
    finally:
        rcs = {}
        for p, f, _lp, name in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p, f, _lp, name in procs:
            try:
                rcs[name] = p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=30)
                rcs[name] = "killed"
            f.close()
    # graceful-drain contract checked AFTER all children are reaped
    assert rcs == {"global": 0, "proxy": 0, "local": 0}, rcs
