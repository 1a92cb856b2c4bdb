"""OpenTracing adapter + flush-stage self-spans
(reference trace/opentracing.go; flusher.go:29 span-wrapped stages)."""

import time

import pytest

from veneur_tpu.trace.opentracing import (
    DEFAULT_HEADER_FORMAT, HEADER_FORMATS, GLOBAL_TRACER, OpenTracingTracer,
    SpanContext)
from veneur_tpu.trace.tracer import Span


# -- carrier inject/extract ---------------------------------------------------

def test_inject_writes_envoy_format_with_sampled_header():
    span = Span("op", service="svc")
    headers = {}
    GLOBAL_TRACER.inject(span, headers)
    assert headers["ot-tracer-traceid"] == format(span.trace_id, "x")
    assert headers["ot-tracer-spanid"] == format(span.id, "x")
    assert headers["ot-tracer-sampled"] == "true"


def test_extract_all_four_header_conventions():
    t = OpenTracingTracer()
    cases = [
        ({"ot-tracer-traceid": format(0xabc123, "x"),
          "ot-tracer-spanid": format(0xdef456, "x")}, 0xabc123, 0xdef456),
        ({"Trace-Id": "123", "Span-Id": "456"}, 123, 456),
        ({"X-Trace-Id": "789", "X-Span-Id": "1011"}, 789, 1011),
        ({"Traceid": "1213", "Spanid": "1415"}, 1213, 1415),
    ]
    for headers, want_t, want_s in cases:
        ctx = t.extract_context(headers)
        assert ctx is not None, headers
        assert ctx.trace_id == want_t
        assert ctx.span_id == want_s


def test_extract_is_case_insensitive_and_respects_precedence():
    t = OpenTracingTracer()
    # envoy headers win over OT-format headers when both present
    ctx = t.extract_context({"OT-TRACER-TRACEID": "ff", "ot-tracer-spanid": "10",
                     "Trace-Id": "999", "Span-Id": "888"})
    assert ctx.trace_id == 0xff and ctx.span_id == 0x10


def test_extract_falls_through_malformed_convention():
    t = OpenTracingTracer()
    # broken envoy values -> the decimal OT headers are used instead
    ctx = t.extract_context({"ot-tracer-traceid": "zzz", "ot-tracer-spanid": "q",
                     "Trace-Id": "42", "Span-Id": "43"})
    assert ctx.trace_id == 42 and ctx.span_id == 43
    assert t.extract_context({"unrelated": "1"}) is None
    # int64 overflow falls through to the next convention (Go ParseInt)
    big = format(2 ** 64 - 1, "x")
    ctx = t.extract_context({"ot-tracer-traceid": big,
                             "ot-tracer-spanid": "10",
                             "Trace-Id": "42", "Span-Id": "43"})
    assert ctx.trace_id == 42 and ctx.span_id == 43


def test_inject_extract_round_trip_every_format():
    t = OpenTracingTracer()
    span = Span("op")
    for fmt in HEADER_FORMATS:
        headers = {}
        t.inject(span, headers, header_format=fmt)
        ctx = t.extract_context(headers)
        assert ctx.trace_id == span.trace_id
        assert ctx.span_id == span.id


def test_extract_request_child_links_parent():
    t = OpenTracingTracer(service="svc")
    parent = Span("client-op")
    headers = {}
    t.inject_header(parent, headers)
    child = t.extract_request_child("/import", headers, "server-op")
    assert child.trace_id == parent.trace_id
    assert child.parent_id == parent.id
    assert child.id != parent.id
    assert child.tags["resource"] == "/import"
    assert t.extract_request_child("/import", {}, "x") is None


# -- span context / baggage ---------------------------------------------------

def test_span_context_baggage_case_insensitive():
    ctx = SpanContext({"TraceId": "7", "SpanID": "8", "parentid": "9",
                       "Resource": "/x"})
    assert ctx.trace_id == 7 and ctx.span_id == 8 and ctx.parent_id == 9
    assert ctx.resource == "/x"
    ctx.set_baggage_item("k", "v")
    assert ctx.baggage_item("K") == "v"
    assert SpanContext({"traceid": "notanint"}).trace_id == 0


def test_span_opentracing_methods():
    s = Span("op")
    assert s.set_tag("num", 3) is s
    assert s.tags["num"] == "3"
    s.set_operation_name("/resource")
    assert s.tags["resource"] == "/resource"
    s.log_kv("event", "flushed", "count", 5)
    assert s.log_lines == [{"event": "flushed", "count": 5}]
    assert s.context().trace_id == s.trace_id


# -- flush-stage self-spans ---------------------------------------------------

def test_flush_produces_span_tree_in_debug_span_sink():
    """flusher.go:29: the flush is span-wrapped per stage; the tree must
    be observable through a debug span sink via the channel client."""
    from tests.test_server import small_config, _send_udp, _wait_processed
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink, DebugSpanSink

    ssink = DebugSpanSink()
    srv = Server(small_config(), metric_sinks=[DebugMetricSink()],
                 span_sinks=[ssink])
    srv.start()
    try:
        _send_udp(srv.local_addr(), [b"sp.count:1|c", b"sp.t:3|ms"])
        _wait_processed(srv, 2)
        assert srv.trigger_flush()
        # the spans loop back through the span pipeline, whose sinks are
        # flushed by the NEXT flush
        assert srv.trigger_flush()
        want = {"flush", "flush.device_update", "flush.sinks",
                "flush.sink.debug"}

        def complete_trace():
            # one flush's spans can straddle a flush boundary: wait for
            # a single trace that holds the whole tree
            by_trace = {}
            for s in list(ssink.spans):
                by_trace.setdefault(s.trace_id, {}).setdefault(s.name, s)
            for tree in by_trace.values():
                if want <= set(tree):
                    return tree
            return None

        deadline = time.time() + 30
        by_name = complete_trace()
        while by_name is None and time.time() < deadline:
            time.sleep(0.05)
            by_name = complete_trace()
        assert by_name is not None, sorted({s.name for s in ssink.spans})
        root = by_name["flush"]
        # the stage spans the flush worker's one stage() helper makes
        # (server._flush_stage), under the names README §Monitoring gives
        for stage in ("flush.device_update", "flush.sinks"):
            child = by_name[stage]
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.id
        sink_span = by_name.get("flush.sink.debug")
        assert sink_span is not None, sorted(by_name)
        assert sink_span.parent_id == by_name["flush.sinks"].id
        assert root.service == "veneur"
        assert root.end_timestamp >= root.start_timestamp
    finally:
        srv.shutdown()


def test_http_import_continues_forwarders_trace():
    """The /import handler extracts the poster's trace headers
    (handlers_global.go:126) and its request span joins that trace."""
    from tests.test_server import small_config
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink, DebugSpanSink
    import urllib.request

    ssink = DebugSpanSink()
    srv = Server(small_config(http_address="127.0.0.1:0"),
                 metric_sinks=[DebugMetricSink()], span_sinks=[ssink])
    srv.start()
    try:
        parent = Span("forwarder")
        headers = {"Content-Type": "application/json"}
        GLOBAL_TRACER.inject_header(parent, headers)
        body = (b'[{"name":"ot.c","type":"counter","tagstring":"",'
                b'"tags":[],"value":"CgAAAAAAAAA="}]')
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.http_port}/import", data=body,
            method="POST", headers=headers)
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 202
        deadline = time.time() + 10
        found = None
        while time.time() < deadline and found is None:
            found = next((s for s in ssink.spans
                          if s.name == "veneur.opentracing.import"), None)
            time.sleep(0.05)
        assert found is not None
        assert found.trace_id == parent.trace_id
        assert found.parent_id == parent.id
    finally:
        srv.shutdown()


# -- StartSpan references / baggage / finish options (opentracing.go:403) ----

def test_start_span_child_of_span_and_context():
    from veneur_tpu.trace.opentracing import (
        OpenTracingTracer, SpanContext, span_context)
    tr = OpenTracingTracer(service="svc")
    root = tr.start_span_ot("root")
    assert root.parent_id == 0 and root.name == "root"

    child = tr.start_span_ot("c1", child_of=root)
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.id

    # a raw SpanContext works as the reference too
    ctx = span_context(root)
    child2 = tr.start_span_ot("c2", child_of=ctx)
    assert child2.trace_id == root.trace_id
    assert child2.parent_id == root.id


def test_follows_from_treated_as_child_of():
    """opentracing.go:430: FollowsFromRef falls through to ChildOfRef."""
    from veneur_tpu.trace.opentracing import OpenTracingTracer
    tr = OpenTracingTracer(service="svc")
    root = tr.start_span_ot("root")
    f = tr.start_span_ot("f", follows_from=root)
    c = tr.start_span_ot("c", child_of=root)
    assert (f.trace_id, f.parent_id) == (c.trace_id, c.parent_id)


def test_start_span_name_tag_override_and_caller_fallback():
    from veneur_tpu.trace.opentracing import OpenTracingTracer
    tr = OpenTracingTracer(service="svc")
    s = tr.start_span_ot("orig", tags={"name": "renamed", "k": "v"})
    assert s.name == "renamed" and s.tags["k"] == "v"
    anon = tr.start_span_ot("")
    assert anon.name == \
        "test_start_span_name_tag_override_and_caller_fallback"


def test_baggage_propagates_to_children_not_identity():
    from veneur_tpu.trace.opentracing import OpenTracingTracer
    tr = OpenTracingTracer(service="svc")
    root = tr.start_span_ot("root")
    root.set_baggage_item("tenant", "t-9")
    assert root.baggage_item("TENANT") == "t-9"   # case-insensitive read
    child = tr.start_span_ot("c", child_of=root)
    assert child.baggage_item("tenant") == "t-9"
    # identity keys come from the span ids, never from baggage
    assert child.trace_id == root.trace_id and child.parent_id == root.id


def test_finish_with_options_and_log_records():
    import time as _t
    from veneur_tpu.trace.opentracing import OpenTracingTracer
    tr = OpenTracingTracer(service="svc")
    s = tr.start_span_ot("op", start_time_ns=1_000)
    s.log_kv("event", "retry", "attempt", 2)
    end = int(_t.time() * 1e9)
    ssf = s.finish_with_options(finish_time_ns=end,
                                log_records=[{"msg": "done"}])
    assert ssf.start_timestamp == 1_000 and ssf.end_timestamp == end
    # records retained but never serialized into SSF — the reference
    # ignores log data on the wire (opentracing.go:312)
    assert s.log_lines == [{"event": "retry", "attempt": 2},
                           {"msg": "done"}]
    assert not any("retry" in str(t) for t in ssf.tags.values())
    # deprecated interface-compat no-ops exist and do nothing
    s.log_event("x")
    s.log_event_with_payload("x", {"y": 1})
    s.log(None)
    assert s.log_lines[-1] == {"msg": "done"}
