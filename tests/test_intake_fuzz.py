"""Random-bytes fuzz of the network intake surfaces: whatever arrives,
listeners must answer with the right status (HTTP) or keep reading
(UDP) — never die or 500. The pipeline-thread DoS class (set members,
events) was found by fuzz; these pin the transport layer the same way."""

import io
import socket
import struct
import time
import urllib.error
import urllib.request
import zlib

import numpy as np
import pytest

from veneur_tpu.protocol.wire import (MAX_SSF_PACKET_LENGTH, FramingError,
                                      parse_ssf, read_ssf, write_ssf)
from veneur_tpu.samplers.parser import (ParseError, parse_event,
                                        parse_metric, parse_service_check)
from veneur_tpu.server.server import Server
from veneur_tpu.sinks.debug import DebugMetricSink, DebugSpanSink

from tests.test_server import _wait_until, small_config


def test_http_import_random_bodies_never_5xx():
    srv = Server(small_config(http_address="127.0.0.1:0"),
                 metric_sinks=[DebugMetricSink()])
    srv.start()
    url = f"http://127.0.0.1:{srv.http_port}/import"
    rng = np.random.default_rng(9)
    codes: dict = {}
    try:
        for i in range(150):
            n = int(rng.integers(0, 300))
            body = bytes(rng.integers(0, 256, n).astype(np.uint8))
            if i % 3 == 0:
                body = zlib.compress(body)
            headers = {"Content-Type": [
                "application/json", "application/x-protobuf",
                "application/octet-stream"][i % 3]}
            if i % 2 == 0:
                headers["Content-Encoding"] = "deflate"
            req = urllib.request.Request(url, data=body, method="POST",
                                         headers=headers)
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    codes[r.status] = codes.get(r.status, 0) + 1
            except urllib.error.HTTPError as e:
                codes[e.code] = codes.get(e.code, 0) + 1
        assert all(c < 500 for c in codes), codes
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http_port}/healthcheck",
                timeout=10) as r:
            assert r.status == 200
    finally:
        srv.shutdown()


def test_ssf_udp_random_datagrams_keep_reader_alive():
    ssink = DebugSpanSink()
    srv = Server(small_config(statsd_listen_addresses=[],
                              ssf_listen_addresses=["udp://127.0.0.1:0"]),
                 metric_sinks=[DebugMetricSink()], span_sinks=[ssink])
    srv.start()
    try:
        rng = np.random.default_rng(4)
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for _ in range(500):
            n = int(rng.integers(0, 400))
            s.sendto(bytes(rng.integers(0, 256, n).astype(np.uint8)),
                     srv.local_addr())
        # a valid span afterward proves the reader survived. UDP may drop
        # it with the garbage: a starved reader lets the socket buffer
        # fill (the server then counts fewer than the 500 it was sent, and
        # goes on reading), so the probe is sent again on every turn
        from veneur_tpu.proto import ssf_pb2
        sp = ssf_pb2.SSFSpan(version=0, trace_id=9, id=9, service="alive",
                             name="ok", start_timestamp=1, end_timestamp=2)
        deadline = time.time() + 60
        while time.time() < deadline and not any(
                x.name == "ok" for x in ssink.spans):
            s.sendto(sp.SerializeToString(), srv.local_addr())
            time.sleep(0.05)
        s.close()
        assert any(x.name == "ok" for x in ssink.spans), "reader died"
    finally:
        srv.shutdown()

# -- malformed-datagram corpus (overload hardening) --------------------------
# A parser that raises anything but ParseError under garbage input kills
# the pipeline thread — the single worst failure mode under overload,
# when garbage is most likely (truncated datagrams from full socket
# buffers). The corpus enumerates the malformation classes by hand; the
# random fuzzers above cover the space between them.

MALFORMED_METRIC_CORPUS = [
    # truncated at every plausible boundary
    b"", b":", b"|", b"a", b"a:", b"a:1", b"a:1|", b"a:1|c|", b"a:1|c|@",
    b"a:1|c|#", b"a:1|c|@0.5|", b"a:|c", b"a:1|c|@|#t:1",
    # zero-length names
    b":1|c", b":|c", b":1|ms|#tag:v",
    # NaN / Inf / absurd numerics
    b"a:nan|c", b"a:NaN|g", b"a:inf|c", b"a:-inf|ms", b"a:Infinity|h",
    b"a:1e400|c", b"a:-1e400|g", b"a:0x10|c", b"a:1_000|c", b"a:++1|c",
    # bad sample rates
    b"a:1|c|@nan", b"a:1|c|@inf", b"a:1|c|@-1", b"a:1|c|@0",
    b"a:1|c|@2abc", b"a:1|c|@",
    # bad types
    b"a:1|x", b"a:1|cc", b"a:1|\xff", b"a:1|", b"a:1|9",
    # oversized tag sets / tag abuse
    b"a:1|c|#" + b",".join(b"tag%d:%s" % (i, b"v" * 64)
                           for i in range(200)),
    b"a:1|c|#" + b"t" * 65536,
    b"a:1|c|#,,,,", b"a:1|c|##", b"a:1|c|#:",
    # invalid UTF-8 in every field
    b"\xff\xfe:1|c", b"a\x80b:1|c", b"a:1|c|#\xc3:\x28",
    b"s\xf0\x28\x8c\x28:m|s", b"a:\xff|s",
    # embedded NULs and control bytes
    b"a\x00b:1|c", b"a:1\x00|c", b"a:1|c|#t:\x00",
    # multiple colons / pipes in odd places
    b"a:b:c|g", b"a:1|c|c|c|c", b"||||", b"::::",
]


def test_parse_metric_corpus_never_raises_unexpectedly():
    for pkt in MALFORMED_METRIC_CORPUS:
        try:
            parse_metric(pkt)
        except ParseError:
            pass  # the one sanctioned rejection path
        except Exception as e:
            pytest.fail(f"parse_metric({pkt!r}) leaked "
                        f"{type(e).__name__}: {e}")


MALFORMED_EVENT_CORPUS = [
    b"_e{", b"_e{}", b"_e{}:", b"_e{1,1}:", b"_e{0,0}:|",
    b"_e{99,99}:short|x", b"_e{nan,1}:a|b", b"_e{-1,-1}:a|b",
    b"_e{1,1}:a|b|x:", b"_e{1,1}:a|b|d:nan", b"_e{1,1}:a|b|p:bogus",
    b"_e{1,1}:a|b|t:bogus", b"_e{1,1}:\xff|\xfe",
    b"_e{18446744073709551616,1}:a|b",
]

MALFORMED_CHECK_CORPUS = [
    b"_sc", b"_sc|", b"_sc|name", b"_sc|name|", b"_sc|name|9",
    b"_sc|name|nan", b"_sc||0", b"_sc|name|0|d:nan", b"_sc|name|0|x:",
    b"_sc|\xff\xfe|0", b"_sc|name|0|m:\xc3\x28",
]


def test_parse_event_and_check_corpus_never_raise_unexpectedly():
    for fn, corpus in ((parse_event, MALFORMED_EVENT_CORPUS),
                       (parse_service_check, MALFORMED_CHECK_CORPUS)):
        for pkt in corpus:
            try:
                fn(pkt, now=1)
            except ParseError:
                pass
            except Exception as e:
                pytest.fail(f"{fn.__name__}({pkt!r}) leaked "
                            f"{type(e).__name__}: {e}")


def _ssf_frames():
    """Malformed SSF frame corpus: (stream_bytes, why)."""
    from veneur_tpu.proto import ssf_pb2
    good = ssf_pb2.SSFSpan(version=0, trace_id=1, id=2, service="s",
                           name="n", start_timestamp=1, end_timestamp=2)
    buf = io.BytesIO()
    write_ssf(buf, good)
    frame = buf.getvalue()
    return [
        (frame[:1], "truncated before length"),
        (frame[:3], "truncated mid-length"),
        (frame[:6], "truncated mid-body"),
        (b"\x01" + frame[1:], "unknown version"),
        (b"\xff" * 5, "garbage header"),
        (struct.pack(">BI", 0, MAX_SSF_PACKET_LENGTH + 1),
         "oversized length"),
        (struct.pack(">BI", 0, 8) + b"\xde\xad\xbe\xef\xde\xad\xbe\xef",
         "valid frame, garbage protobuf"),
    ]


def test_read_ssf_corpus_raises_only_framing_or_decode_errors():
    from google.protobuf.message import DecodeError
    for raw, why in _ssf_frames():
        try:
            read_ssf(io.BytesIO(raw))
        except (FramingError, DecodeError):
            pass  # framing errors are fatal-per-connection by contract
        except Exception as e:
            pytest.fail(f"read_ssf({why}) leaked {type(e).__name__}: {e}")
    # clean EOF at a boundary is None, not an error
    assert read_ssf(io.BytesIO(b"")) is None


def test_parse_ssf_garbage_raises_only_decode_error():
    from google.protobuf.message import DecodeError
    rng = np.random.default_rng(11)
    for n in (1, 2, 7, 33, 257):
        blob = bytes(rng.integers(0, 256, n).astype(np.uint8))
        try:
            parse_ssf(blob)
        except DecodeError:
            pass
        except Exception as e:
            pytest.fail(f"parse_ssf({n}B garbage) leaked "
                        f"{type(e).__name__}: {e}")


# -- malformed-envelope corpus (exactly-once forwarding) ---------------------
# The (source_id, epoch, seq) envelope is attacker-reachable surface on
# the global tier's /import: a malformed one must be REJECTED with
# accounting (veneur.forward.envelope_rejected_total), never folded and
# never fatal; a duplicate/regressing seq must be SUPPRESSED WITH a 202
# (the ack the sender needs to evict its unit), counted in
# veneur.forward.dup_suppressed_total.

_SID_OK = "0123456789abcdef0123456789abcdef"

# header dicts that must 400 + count one rejection each.
# forward_dedup_window=8 in the test server -> max seq skip 8*64 = 512.
ENVELOPE_REJECT_CORPUS = [
    # partial envelopes: half-present is corruption, not a legacy peer
    {"veneur-source-id": _SID_OK},
    {"veneur-epoch": "0", "veneur-seq": "0"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0"},
    {"veneur-seq": "0"},
    # wrong source_id shapes (length, case, charset)
    {"veneur-source-id": "abcd", "veneur-epoch": "0", "veneur-seq": "0"},
    {"veneur-source-id": _SID_OK * 2, "veneur-epoch": "0",
     "veneur-seq": "0"},
    {"veneur-source-id": _SID_OK.upper(), "veneur-epoch": "0",
     "veneur-seq": "0"},
    {"veneur-source-id": "zz" * 16, "veneur-epoch": "0",
     "veneur-seq": "0"},
    # non-integer / negative epoch and seq
    {"veneur-source-id": _SID_OK, "veneur-epoch": "x", "veneur-seq": "0"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
     "veneur-seq": "1.5"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "-1",
     "veneur-seq": "0"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
     "veneur-seq": "-2"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
     "veneur-seq": "nan"},
    # a seq skip past the window bound must not wipe the bitmap
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
     "veneur-seq": "513"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
     "veneur-seq": str(10 ** 18)},
    # trace context travels as a pair: half-present is corruption (a
    # legacy peer omits BOTH keys — that stays a 202, asserted below)
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0", "veneur-seq": "0",
     "veneur-trace-id": "7"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0", "veneur-seq": "0",
     "veneur-parent-span-id": "7"},
    # non-integer / non-positive ids
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0", "veneur-seq": "0",
     "veneur-trace-id": "x", "veneur-parent-span-id": "7"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0", "veneur-seq": "0",
     "veneur-trace-id": "7", "veneur-parent-span-id": "1.5"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0", "veneur-seq": "0",
     "veneur-trace-id": "0", "veneur-parent-span-id": "7"},
    {"veneur-source-id": _SID_OK, "veneur-epoch": "0", "veneur-seq": "0",
     "veneur-trace-id": "7", "veneur-parent-span-id": "-3"},
]

# wrapped-body envelopes that must 400 + count one rejection each
ENVELOPE_REJECT_BODY_CORPUS = [
    "notadict", 7, ["x"],
    {"source_id": _SID_OK, "epoch": "x", "seq": 0},
    {"source_id": _SID_OK, "epoch": 0},
    {"source_id": "short", "epoch": 0, "seq": 0},
    {"source_id": _SID_OK, "epoch": 0, "seq": -1},
    # partial / malformed trace context in wrapped-body form
    {"source_id": _SID_OK, "epoch": 0, "seq": 0, "trace_id": 7},
    {"source_id": _SID_OK, "epoch": 0, "seq": 0, "parent_span_id": 7},
    {"source_id": _SID_OK, "epoch": 0, "seq": 0,
     "trace_id": "x", "parent_span_id": 7},
    {"source_id": _SID_OK, "epoch": 0, "seq": 0,
     "trace_id": 7, "parent_span_id": 0},
]


def _counter_jm(name="env.fuzz", value=3):
    import base64
    from veneur_tpu.forward import gob
    return {"name": name, "type": "counter", "tagstring": "",
            "tags": [],
            "value": base64.b64encode(
                bytes(gob.encode_counter(value))).decode()}


def _post_import(port, body, headers=None):
    import json
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/import",
        data=json.dumps(body).encode(), method="POST", headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status
    except urllib.error.HTTPError as e:
        return e.code


def test_envelope_corpus_rejections_all_accounted():
    """Every malformed envelope — header or wrapped-body form — 400s,
    increments veneur.forward.envelope_rejected_total exactly once, and
    never folds; duplicate and regressing seqs are suppressed WITH a 202
    and counted; the server survives to import a clean batch after."""
    sink = DebugMetricSink()
    srv = Server(small_config(http_address="127.0.0.1:0",
                              forward_dedup_window=8),
                 metric_sinks=[sink])
    srv.start()
    port = srv.http_port
    try:
        for hdrs in ENVELOPE_REJECT_CORPUS:
            assert _post_import(port, [_counter_jm()], hdrs) == 400, hdrs
        for env in ENVELOPE_REJECT_BODY_CORPUS:
            assert _post_import(
                port, {"envelope": env, "metrics": [_counter_jm()]}
            ) == 400, env
        rejected = len(ENVELOPE_REJECT_CORPUS) \
            + len(ENVELOPE_REJECT_BODY_CORPUS)
        assert srv._c_envelope_rejected.value() == float(rejected)
        # rejections landed in the registered counter, visible to ops
        assert srv.metrics.flat_values()[
            "veneur.forward.envelope_rejected_total"] == float(rejected)

        # duplicate seq: suppressed, ACKED (202), counted — NOT folded
        ok_env = {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
                  "veneur-seq": "5"}
        assert _post_import(port, [_counter_jm()], ok_env) == 202
        assert _post_import(port, [_counter_jm()], ok_env) == 202
        assert srv._c_dup_suppressed.value() == 1.0
        # a WELL-FORMED trace-context pair on a fresh seq imports and
        # folds like any other batch (PR-11 cross-tier tracing)
        traced = {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
                  "veneur-seq": "6", "veneur-trace-id": "7",
                  "veneur-parent-span-id": "9"}
        assert _post_import(port, [_counter_jm()], traced) == 202
        # a fresh forward jump (within max_skip) folds and drags the
        # window forward so a regressing seq drops past its reach...
        jump = {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
                "veneur-seq": "100"}
        assert _post_import(port, [_counter_jm()], jump) == 202
        # ...making seq 3 STALE: suppressed conservatively, still 202
        old = {"veneur-source-id": _SID_OK, "veneur-epoch": "0",
               "veneur-seq": "3"}
        assert _post_import(port, [_counter_jm()], old) == 202
        assert srv._c_dup_suppressed.value() == 2.0

        # the pipeline survived all of it, and only the fresh imports
        # (seq 5, traced seq 6, seq 100, a legacy unenveloped batch)
        # ever folded: env.fuzz == 3 folds x 3, despite the dozens of
        # batches carrying it
        before = srv.aggregator.processed
        assert _post_import(port, [_counter_jm("env.legacy")]) == 202
        _wait_until(lambda: srv.aggregator.processed > before,
                    60, "clean imports after the corpus")
        srv.trigger_flush()
        from tests.test_server import by_name
        flushed = by_name(sink.flushed)
        assert flushed["env.fuzz"].value == 9.0
        assert flushed["env.legacy"].value == 3.0
    finally:
        srv.shutdown()


def test_grpc_envelope_rejections_accounted_and_not_acked():
    """The gRPC flavor of the same contract: malformed metadata aborts
    INVALID_ARGUMENT (counted server-side; the sender does NOT treat it
    as an ack), a valid envelope imports, its duplicate is suppressed
    but the RPC still SUCCEEDS (that success is the ack)."""
    import grpc as _grpc

    from veneur_tpu.forward.envelope import Envelope
    from veneur_tpu.forward.rpc import ForwardClient

    srv = Server(small_config(grpc_address="127.0.0.1:0",
                              forward_dedup_window=8),
                 metric_sinks=[DebugMetricSink()])
    srv.start()
    client = ForwardClient(f"127.0.0.1:{srv.grpc_port}")
    try:
        bad = Envelope("tooshort", 0, 0)          # never validated client-side
        with pytest.raises(_grpc.RpcError) as ei:
            client.send_metrics([], envelope=bad)
        assert ei.value.code() == _grpc.StatusCode.INVALID_ARGUMENT
        assert srv._c_envelope_rejected.value() == 1.0

        good = Envelope(_SID_OK, 0, 0)
        client.send_metrics([], envelope=good)    # fresh: imported
        client.send_metrics([], envelope=good)    # duplicate: acked anyway
        assert srv._c_dup_suppressed.value() == 1.0
    finally:
        client.close()
        srv.shutdown()


# -- tenant-tag extraction corpus (multi-tenant fairness) --------------------
# Tenant identity is extracted from RAW datagram bytes at the ring
# admission boundary (dogstatsd.cpp tenant_extract) and mirrored in
# Python (reliability/tenancy.py extract_tenant). Every malformation
# must resolve to the default tenant — never a drop, never a crash —
# and the two implementations must agree byte-for-byte: a divergence
# would charge the same datagram to different tenants depending on
# which ingest path carried it.

TENANT_CORPUS = [
    # (datagram, expected tenant; None = default)
    (b"a:1|c|#tenant:acme", "acme"),
    (b"a:1|c|#env:prod,tenant:acme,zone:b", "acme"),
    (b"a:1|c|#tenant:ab|@0.5", "ab"),                 # value ends at |
    (b"a:1|c|#tenant:ab\nb:2|c", "ab"),               # value ends at newline
    (b"a:1|c|#tenant:ac", "ac"),                      # value ends at EOD
    (b"a:1|c|#tenant:" + b"x" * 64, "x" * 64),        # exactly at the cap
    (b"caf\xc3\xa9:1|c|#tenant:caf\xc3\xa9",
     b"caf\xc3\xa9".decode("utf-8")),                 # valid multibyte
    # missing tag entirely
    (b"a:1|c", None),
    (b"a:1|c|#env:prod", None),
    # duplicate tags: the FIRST well-formed occurrence wins, even when
    # a later one differs — tenants cannot self-reassign mid-datagram
    (b"a:1|c|#tenant:a,tenant:b", "a"),
    # ...and a first occurrence with a bad value resolves the datagram
    # to default (anomaly => default, never keep scanning: a crafted
    # datagram must not pick which of its candidate values is charged)
    (b"a:1|c|#tenant:,tenant:x", None),
    # empty / oversized / invalid-UTF-8 values
    (b"a:1|c|#tenant:", None),
    (b"a:1|c|#tenant:,env:x", None),
    (b"a:1|c|#tenant:" + b"x" * 65, None),
    (b"a:1|c|#tenant:\xff\xfe", None),
    (b"a:1|c|#tenant:\xc0\xaf", None),                # C0 lead byte
    (b"a:1|c|#tenant:ab\xe2\x28", None),              # broken continuation
    # the tag must sit at a tag-section boundary ('#' or ','), not in
    # the metric name or inside another tag's value
    (b"tenant:acme:1|c", None),
    (b"a:1|c|#xtenant:evil", None),
    (b"a:1|c|#note:tenant:evil", None),
    (b"a:1|c|#xtenant:evil,tenant:good", "good"),
    # tag split across a truncated datagram (full socket buffer)
    (b"a:1|c|#tena", None),
    (b"a:1|c|#tenant", None),
    (b"a:1|c|#,tenant:ok", "ok"),
]


def test_tenant_extract_corpus_and_parity():
    """Every corpus row resolves as specified, in the Python mirror AND
    (when buildable) the C++ extractor — byte-for-byte agreement."""
    from veneur_tpu import native
    from veneur_tpu.reliability.tenancy import extract_tenant
    have_native = native.available()
    for data, want in TENANT_CORPUS:
        got = extract_tenant("tenant:", data)
        assert got == want, (data, got, want)
        if have_native:
            got_c = native.tenant_extract("tenant:", data)
            assert got_c == want, ("native", data, got_c, want)


def test_tenant_extract_random_parity():
    """Random structured fuzz around the tag: the two extractors must
    agree on arbitrary byte soup, not just the hand-picked corpus."""
    from veneur_tpu import native
    from veneur_tpu.reliability.tenancy import extract_tenant
    if not native.available():
        pytest.skip("native engine not buildable")
    rng = np.random.default_rng(21)
    frags = [b"#", b",", b"|", b"tenant:", b"tenant", b":", b"\n",
             b"\xff", b"\xc3\xa9", b"a", b"zz", b"" ]
    for _ in range(2000):
        n = int(rng.integers(0, 12))
        data = b"m:1|c" + b"".join(
            frags[int(rng.integers(0, len(frags)))] for _ in range(n))
        py = extract_tenant("tenant:", data)
        cc = native.tenant_extract("tenant:", data)
        assert py == cc, (data, py, cc)


def test_tenant_corpus_every_row_accounted():
    """The corpus through the REAL ring admission boundary: every
    datagram lands in exactly one tenant's admitted count (admission
    off => everything admits, but per-tenant accounting still runs),
    and malformed identities all land on default."""
    from veneur_tpu import native
    if not native.available():
        pytest.skip("native engine not buildable")
    from veneur_tpu.aggregation.host import BatchSpec
    from veneur_tpu.aggregation.state import TableSpec
    spec = TableSpec(counter_capacity=256, gauge_capacity=64,
                     status_capacity=16, set_capacity=32,
                     histo_capacity=64)
    bspec = BatchSpec(counter=256, gauge=128, status=16, set=64, histo=256)
    eng = native.NativeIngest(spec, bspec)
    eng.tenant_config(True)
    eng.rings_start(2, fds=None, max_len=4096, ring_cap=4096)
    try:
        want: dict = {}
        for i, (data, tenant) in enumerate(TENANT_CORPUS):
            assert eng.rings_inject(i % 2, data)
            want[tenant or "default"] = want.get(tenant or "default", 0) + 1
        deadline = time.time() + 30
        while time.time() < deadline:
            d = eng.admission_drain().get("tenants", {})
            if d:
                break
            time.sleep(0.05)
        got = {t: sum(ent.get("admitted", {}).values())
               + sum(ent.get("shed", {}).values())
               for t, ent in d.items()}
        # late stragglers: fold any second drain
        time.sleep(0.2)
        for t, ent in eng.admission_drain().get("tenants", {}).items():
            got[t] = got.get(t, 0) \
                + sum(ent.get("admitted", {}).values()) \
                + sum(ent.get("shed", {}).values())
        assert got == want, (got, want)
        assert sum(got.values()) == len(TENANT_CORPUS)
    finally:
        eng.readers_stop()


def test_server_accounts_every_corpus_rejection():
    """End to end: the full malformed corpus over real UDP. Every
    datagram must land in processed or in the registered drop counter
    (veneur.parse_errors_total) — shed, not lost — and the pipeline
    thread must survive to flush a valid metric afterward."""
    sink = DebugMetricSink()
    srv = Server(small_config(native_ingest=False), metric_sinks=[sink])
    srv.start()
    try:
        addr = srv.local_addr()
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        # empty payloads don't traverse UDP and the 64KiB tag entry
        # exceeds the datagram limit — both stay parser-level-only
        corpus = [p for p in (MALFORMED_METRIC_CORPUS
                              + MALFORMED_EVENT_CORPUS
                              + MALFORMED_CHECK_CORPUS)
                  if p and len(p) < 60000]
        for pkt in corpus:
            s.sendto(pkt, addr)
        s.sendto(b"fuzz.survivor:1|c", addr)
        s.close()

        def accounted():
            return (srv.aggregator.processed + srv.parse_errors
                    + srv.aggregator.extra_parse_errors()) >= \
                len(corpus) + 1
        _wait_until(accounted, 60, "corpus fully accounted")
        # rejections landed in the REGISTERED counter, not a shadow int
        assert srv.metrics.flat_values()["veneur.parse_errors_total"] \
            == float(srv.parse_errors)
        assert srv.parse_errors > 0
        assert srv.trigger_flush(wait=True, timeout=120)
        assert any(m.name == "fuzz.survivor" for m in sink.flushed)
    finally:
        srv.shutdown()
