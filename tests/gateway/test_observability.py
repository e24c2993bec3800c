"""Observability: request traces, X-Request-Id echo, gateway /metrics,
exposition-format validity, percentile interpolation, event-bus drop
accounting."""

import asyncio
import re

import pytest

from llmlb_tpu.engine.metrics import EngineMetrics, Histogram
from llmlb_tpu.gateway.events import DashboardEventBus
from llmlb_tpu.gateway.metrics import GatewayMetrics
from llmlb_tpu.gateway.tracing import (
    SPAN_ORDER,
    RequestTrace,
    TraceStore,
    mint_request_id,
)
from tests.support import GatewayHarness, MockOpenAIEndpoint

# ------------------------------------------------------- exposition validity

_SAMPLE_RE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(-?[0-9.eE+]+)$"
)
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def assert_valid_exposition(text: str) -> dict:
    """Parser-style validity check: every sample belongs to a `# TYPE`d
    family, histogram buckets are cumulative-monotonic with increasing
    edges ending at +Inf, and _count == +Inf bucket with _sum present.
    Returns the parsed histograms keyed by (family, labels)."""
    lines = text.splitlines()
    types: dict[str, str] = {}
    for ln in lines:
        if ln.startswith("# TYPE "):
            _, _, name, mtype = ln.split(" ")
            types[name] = mtype
    hists: dict = {}
    for ln in lines:
        if not ln or ln.startswith("#"):
            continue
        m = _SAMPLE_RE.match(ln)
        assert m, f"unparseable sample line: {ln!r}"
        name, labels, value = m.group(1), m.group(2) or "", float(m.group(3))
        family = kind = None
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)]
            if name.endswith(suffix) and types.get(base) == "histogram":
                family, kind = base, suffix[1:]
                break
        if family is None:
            assert name in types, f"sample {name!r} has no # TYPE line"
            assert types[name] in ("counter", "gauge")
            continue
        labeldict = dict(_LABEL_RE.findall(labels))
        le = labeldict.pop("le", None)
        key = (family, tuple(sorted(labeldict.items())))
        entry = hists.setdefault(key, {"buckets": [], "sum": None,
                                       "count": None})
        if kind == "bucket":
            assert le is not None, f"{name} bucket without le label"
            entry["buckets"].append((le, value))
        elif kind == "sum":
            entry["sum"] = value
        else:
            entry["count"] = value
    for (family, labelkey), entry in hists.items():
        where = f"{family}{dict(labelkey)}"
        buckets = entry["buckets"]
        assert buckets, f"{where}: histogram with no buckets"
        assert buckets[-1][0] == "+Inf", f"{where}: missing +Inf bucket"
        values = [v for _, v in buckets]
        assert values == sorted(values), f"{where}: buckets not cumulative"
        edges = [float(le) for le, _ in buckets[:-1]]
        assert edges == sorted(edges) and len(set(edges)) == len(edges), (
            f"{where}: bucket edges not strictly increasing"
        )
        assert entry["count"] == values[-1], (
            f"{where}: _count {entry['count']} != +Inf bucket {values[-1]}"
        )
        assert entry["sum"] is not None, f"{where}: missing _sum"
    return hists


def test_engine_metrics_exposition_valid():
    m = EngineMetrics()
    for s in (0.004, 0.02, 0.3, 7.0, 45.0):
        m.record_first_token(s, {})
    for s in (0.002, 0.004, 0.08):
        m.record_itl(s)
    m.record_prefill_step(0.03)
    m.record_decode_step(0.006, active_slots=5)
    m.record_step_phases({"dispatch": 0.001, "compute": 0.004,
                          "fetch": 0.0002, "emit": 0.0001}, slow=True)
    m.record_request_done("stop")
    m.record_request_done("error")
    text = m.render(queue_depth=2, active_slots=5, num_slots=8)
    hists = assert_valid_exposition(text)
    families = {f for f, _ in hists}
    assert families == {
        "llmlb_engine_ttft_seconds", "llmlb_engine_itl_seconds",
        "llmlb_engine_prefill_step_seconds",
        "llmlb_engine_decode_step_seconds",
        "llmlb_engine_schema_compile_seconds",
        "llmlb_engine_step_phase_seconds",
        "llmlb_engine_handoff_latency_seconds",
    }
    assert "llmlb_engine_batch_occupancy 5" in text
    assert "llmlb_engine_slow_steps_total 1" in text
    # every phase of the taxonomy renders its own labeled series, observed
    # or not (dashboards see a complete label set)
    from llmlb_tpu.engine.stepstats import PHASES

    phase_labels = {dict(k).get("phase")
                    for f, k in hists if f == "llmlb_engine_step_phase_seconds"}
    assert phase_labels == set(PHASES)


def test_gateway_metrics_exposition_valid():
    g = GatewayMetrics()
    g.record_request("/v1/chat/completions", 200)
    g.record_request("/v1/chat/completions", 502)
    g.record_retry("chat")
    g.record_queue_timeout("m1")
    for s in (0.004, 0.2, 2.0):
        g.record_ttft("m1", "ep-a", s)
        g.record_e2e("m1", "ep-a", s * 2)
        g.record_queue_wait("m1", "ep-a", s / 4)
    g.record_e2e('weird"model\\name', "ep-b", 0.5)  # label escaping
    text = g.render(
        counters={"llmlb_gateway_dropped_events_total": 3},
        gauges={"llmlb_gateway_active_requests": 1},
    )
    hists = assert_valid_exposition(text)
    families = {f for f, _ in hists}
    assert families == {
        "llmlb_gateway_ttft_seconds", "llmlb_gateway_e2e_seconds",
        "llmlb_gateway_queue_wait_seconds",
    }
    assert 'llmlb_gateway_requests_total{route="/v1/chat/completions",status="502"} 1' in text
    assert 'llmlb_gateway_errors_total{route="/v1/chat/completions"} 1' in text
    assert 'llmlb_gateway_retries_total{api="chat"} 1' in text
    assert 'llmlb_gateway_queue_timeouts_total{model="m1"} 1' in text
    assert "llmlb_gateway_dropped_events_total 3" in text


# ---------------------------------------------------- percentile regression


def test_percentile_interpolates_below_first_edge():
    """A sample entirely below the first bucket edge must not report the
    edge itself (the old behavior)."""
    h = Histogram((1.0, 2.0, 4.0))
    for _ in range(4):
        h.observe(0.5)
    # uniform-within-bucket assumption: p50 of 4 samples in [0, 1] = 0.5
    assert h.percentile(50) == pytest.approx(0.5)
    assert h.percentile(100) == pytest.approx(1.0)


def test_percentile_matches_exact_on_uniform_sample():
    """Uniform data matches the linear-within-bucket assumption exactly, so
    interpolated percentiles should agree with nearest-rank percentiles."""
    sample = [i / 100.0 for i in range(1, 401)]  # 0.01 .. 4.00
    h = Histogram((0.5, 1.0, 2.0, 4.0))
    for v in sample:
        h.observe(v)
    for pct in (10, 25, 50, 75, 90, 99):
        exact = sample[int(len(sample) * pct / 100.0) - 1]
        assert h.percentile(pct) == pytest.approx(exact, rel=0.02), pct


def test_percentile_above_top_edge_reports_max():
    h = Histogram((1.0,))
    h.observe(9.5)
    assert h.percentile(99) == 9.5
    assert Histogram((1.0,)).percentile(50) is None


def test_percentile_empty_histogram_is_none_for_every_pct():
    """Empty histograms must report None at every percentile — not 0, not
    an edge — so /api/health consumers can tell 'no data' from 'fast'."""
    h = Histogram((0.5, 1.0, 2.0))
    for pct in (0.1, 1, 50, 99, 100):
        assert h.percentile(pct) is None
    # and an empty histogram still renders a valid exposition block
    m = EngineMetrics()
    assert_valid_exposition(m.render(queue_depth=0, active_slots=0,
                                     num_slots=1))


def test_percentile_single_bucket_interpolation():
    """All mass in ONE bucket: percentiles interpolate linearly between the
    bucket's lower and upper edge, never snap to an edge."""
    h = Histogram((1.0, 2.0, 4.0))
    for _ in range(10):
        h.observe(1.5)  # lands in (1.0, 2.0]
    # uniform-within-bucket: pN = 1.0 + N/100 * (2.0 - 1.0)
    assert h.percentile(10) == pytest.approx(1.1)
    assert h.percentile(50) == pytest.approx(1.5)
    assert h.percentile(90) == pytest.approx(1.9)
    # single-bucket histogram (one finite edge): same rule against lower=0
    h1 = Histogram((2.0,))
    h1.observe(0.5)
    h1.observe(1.5)
    assert h1.percentile(50) == pytest.approx(1.0)


# ------------------------------------------------------------- tracing unit


def test_mint_request_id_validates_shape():
    assert mint_request_id("abc-123_X.Z:9") == "abc-123_X.Z:9"
    assert mint_request_id(None) != mint_request_id(None)
    assert mint_request_id("bad id with spaces") != "bad id with spaces"
    assert mint_request_id("x" * 200) != "x" * 200


def test_trace_store_ring_bounded():
    store = TraceStore(capacity=3)
    for i in range(5):
        t = store.start(f"t{i}", "POST", "/v1/chat/completions")
        store.finish(t, 200)
    assert len(store) == 3
    assert store.get("t0") is None
    assert store.get("t4")["status"] == 200
    listed = store.list()
    assert [t["trace_id"] for t in listed] == ["t4", "t3", "t2"]
    assert store.list(limit=0) == []
    assert store.list(limit=-5) == []


def test_trace_store_reused_id_does_not_evict_live_trace():
    """Two concurrent requests with the same client-supplied id: the first
    one finishing must not remove the second's in-flight entry."""
    store = TraceStore(capacity=8)
    a = store.start("dup", "POST", "/v1/chat/completions")
    b = store.start("dup", "POST", "/v1/chat/completions")
    store.finish(a, 200)
    live = store.get("dup")
    assert live["in_flight"] is True  # b still observable
    store.finish(b, 200)
    assert store.get("dup")["in_flight"] is False
    assert len(store) == 2


def test_trace_spans_ordered_and_closed_on_finish():
    t = RequestTrace("id1", "POST", "/v1/chat/completions")
    t.begin("auth")
    t.end("auth")
    t.begin("admission")
    t.end("admission")
    t.begin("proxy")  # left open: finish() must close it
    t.finish(200)
    names = [s["name"] for s in t.spans]
    assert names[-1] == "done"
    starts = [s["start_ms"] for s in t.spans]
    assert starts == sorted(starts)
    assert all(s["duration_ms"] is not None and s["duration_ms"] >= 0
               for s in t.spans)


# ---------------------------------------------------------- SLO goodput


def test_record_slo_judges_against_targets():
    from llmlb_tpu.gateway.config import SloConfig

    cfg = SloConfig(ttft_target_s=0.5, itl_target_s=0.05,
                    per_model={"fast": (0.1, 0.01)})
    g = GatewayMetrics(slo=cfg)
    g.record_slo("m", 0.2, 0.01)          # met
    g.record_slo("m", 0.9, 0.01)          # ttft miss
    g.record_slo("m", 0.2, 0.2)           # itl miss
    g.record_slo("m", 0.9, 0.2)           # both miss
    g.record_slo("m", 0.2, None)          # non-streaming: TTFT only, met
    g.record_slo("fast", 0.2, None)       # per-model override: 0.1s → miss
    g.record_slo("m", None, None)         # no first byte: not judged
    text = g.render()
    assert 'llmlb_gateway_slo_eligible_total{model="m"} 5' in text
    assert 'llmlb_gateway_slo_met_total{model="m"} 2' in text
    assert 'llmlb_gateway_slo_ttft_miss_total{model="m"} 2' in text
    assert 'llmlb_gateway_slo_itl_miss_total{model="m"} 2' in text
    assert 'llmlb_gateway_goodput_ratio{model="m"} 0.4' in text
    assert 'llmlb_gateway_slo_ttft_miss_total{model="fast"} 1' in text
    summary = g.summary()
    assert summary["slo_eligible_total"] == 6
    assert summary["goodput_ratio"] == pytest.approx(2 / 6, abs=1e-4)


def test_record_slo_disabled_or_unconfigured_is_inert():
    from llmlb_tpu.gateway.config import SloConfig

    for g in (GatewayMetrics(),  # no config at all
              GatewayMetrics(slo=SloConfig(enabled=False))):
        g.record_slo("m", 0.1, 0.01)
        text = g.render()
        # families still render (dashboards never 404), at zero samples
        assert "# TYPE llmlb_gateway_slo_eligible_total counter" in text
        assert "llmlb_gateway_slo_eligible_total{" not in text
        assert "# TYPE llmlb_gateway_goodput_ratio gauge" in text


def test_slo_config_from_env_parses_overrides(monkeypatch):
    from llmlb_tpu.gateway.config import SloConfig

    monkeypatch.setenv("LLMLB_SLO_TTFT_MS", "1500")
    monkeypatch.setenv("LLMLB_SLO_ITL_MS", "80")
    monkeypatch.setenv("LLMLB_SLO_TARGETS",
                       '{"llama-3-8b": {"ttft_ms": 500, "itl_ms": 50}}')
    cfg = SloConfig.from_env()
    assert cfg.targets_for("other") == (1.5, 0.08)
    assert cfg.targets_for("llama-3-8b") == (0.5, 0.05)
    # malformed JSON degrades to defaults, never raises
    monkeypatch.setenv("LLMLB_SLO_TARGETS", "{not json")
    assert SloConfig.from_env().targets_for("llama-3-8b") == (1.5, 0.08)


# ------------------------------------------------------------ token timeline


def test_token_timeline_bounded_and_payload():
    from llmlb_tpu.gateway.tracing import TokenTimeline

    tl = TokenTimeline()
    for _ in range(TokenTimeline.MAX_MARKS + 10):
        tl.mark()
    assert tl.count == TokenTimeline.MAX_MARKS + 10
    assert len(tl.marks) == TokenTimeline.MAX_MARKS
    payload = tl.payload(tl.marks[0])
    assert payload["truncated"] is True
    assert payload["chunks"] == TokenTimeline.MAX_MARKS + 10
    assert payload["first_ms"] == 0.0
    assert payload["max_gap_ms"] >= 0.0
    assert len(payload["marks_ms"]) == TokenTimeline.MAX_MARKS


def test_trace_store_timeline_sampling_interval():
    store = TraceStore(capacity=4, timeline_interval=3)
    decisions = [store.sample_timeline() for _ in range(9)]
    assert decisions == [True, False, False] * 3
    assert not TraceStore(capacity=4,
                          timeline_interval=0).sample_timeline()


# -------------------------------------------------------- event bus drops


async def test_event_bus_counts_dropped_events():
    bus = DashboardEventBus(queue_size=2)
    sub_id, q = bus.subscribe()
    for i in range(5):
        bus.publish("TpsUpdated", {"i": i})
    await asyncio.sleep(0)  # run the call_soon_threadsafe callbacks
    assert bus.dropped_events(sub_id) == 3
    assert bus.dropped_events_total() == 3
    # the queue kept the NEWEST events (oldest were dropped)
    kept = [q.get_nowait()["data"]["i"] for _ in range(2)]
    assert kept == [3, 4]
    bus.unsubscribe(sub_id)
    assert bus.dropped_events(sub_id) == 0  # per-sub count dies with the sub
    assert bus.dropped_events_total() == 3  # total survives for /metrics


# ------------------------------------------------------------- end to end


async def test_request_id_echoed_and_trace_complete():
    """Acceptance: a completed chat request yields (a) an X-Request-Id
    response header, (b) an ordered auth→done trace with non-negative
    durations, (c) per-model TTFT/e2e/queue-wait histograms at /metrics
    that pass the exposition check."""
    gw = await GatewayHarness.create()
    upstream = await MockOpenAIEndpoint(model="m1").start()
    try:
        gw.register_mock(upstream.url, ["m1"], name="ep-a")
        headers = dict(await gw.inference_headers())
        headers["X-Request-Id"] = "trace-abc-123"
        resp = await gw.client.post(
            "/v1/chat/completions",
            json={"model": "m1",
                  "messages": [{"role": "user", "content": "hi"}]},
            headers=headers,
        )
        assert resp.status == 200, await resp.text()
        # (a) header echoed, client id reused
        assert resp.headers["X-Request-Id"] == "trace-abc-123"
        await resp.read()
        # the proxied upstream call carried the same id (engine joins trace)
        assert upstream.headers_seen[-1]["X-Request-Id"] == "trace-abc-123"

        # (b) the trace is served and well-formed
        t = await gw.client.get("/api/traces/trace-abc-123",
                                headers=await gw.admin_headers())
        assert t.status == 200
        trace = await t.json()
        assert trace["model"] == "m1"
        assert trace["endpoint_name"] == "ep-a"
        assert trace["status"] == 200
        names = [s["name"] for s in trace["spans"]]
        for expected in ("auth", "admission", "queue_wait", "endpoint_select",
                         "proxy", "first_token", "done"):
            assert expected in names, names
        assert names[0] == "auth" and names[-1] == "done"
        assert all(n in SPAN_ORDER for n in names)
        starts = [s["start_ms"] for s in trace["spans"]]
        assert starts == sorted(starts)
        assert all(s["duration_ms"] >= 0 for s in trace["spans"])

        lst = await gw.client.get("/api/traces",
                                  headers=await gw.admin_headers())
        assert lst.status == 200
        assert any(t["trace_id"] == "trace-abc-123"
                   for t in (await lst.json())["traces"])
        missing = await gw.client.get("/api/traces/nope",
                                      headers=await gw.admin_headers())
        assert missing.status == 404

        # (c) gateway /metrics: per-model histograms, valid exposition
        m = await gw.client.get("/metrics")
        assert m.status == 200
        text = await m.text()
        hists = assert_valid_exposition(text)
        for family in ("llmlb_gateway_ttft_seconds",
                       "llmlb_gateway_e2e_seconds",
                       "llmlb_gateway_queue_wait_seconds"):
            labelsets = [dict(k) for f, k in hists if f == family]
            assert any(ls.get("model") == "m1" and ls.get("endpoint") == "ep-a"
                       for ls in labelsets), (family, labelsets)
        assert 'llmlb_gateway_requests_total{route="/v1/chat/completions",status="200"} 1' in text
        assert "llmlb_gateway_dropped_events_total" in text

        # the dashboard overview carries the same figures as JSON
        ov = await gw.client.get("/api/dashboard/overview",
                                 headers=await gw.admin_headers())
        latency = (await ov.json())["latency"]
        assert latency["ttft_s"]["count"] >= 1
        assert latency["e2e_s"]["p50"] is not None
    finally:
        await upstream.stop()
        await gw.close()


async def test_request_id_on_error_paths_and_streams():
    gw = await GatewayHarness.create()
    upstream = await MockOpenAIEndpoint(model="m1").start()
    try:
        gw.register_mock(upstream.url, ["m1"], name="ep-a")
        # error path: unauthenticated request still gets an id
        resp = await gw.client.post("/v1/chat/completions", json={})
        assert resp.status == 401
        assert resp.headers.get("X-Request-Id")
        # a malformed client id is replaced, not echoed
        resp = await gw.client.post(
            "/v1/chat/completions", json={},
            headers={"X-Request-Id": "bad id!! with spaces"},
        )
        assert resp.headers.get("X-Request-Id") not in (None,
                                                        "bad id!! with spaces")
        # streaming: header present on the prepared stream + decode span
        headers = dict(await gw.inference_headers())
        headers["X-Request-Id"] = "trace-stream-1"
        resp = await gw.client.post(
            "/v1/chat/completions",
            json={"model": "m1", "stream": True,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers=headers,
        )
        assert resp.status == 200
        assert resp.headers["X-Request-Id"] == "trace-stream-1"
        body = await resp.text()
        assert "[DONE]" in body
        t = await gw.client.get("/api/traces/trace-stream-1",
                                headers=await gw.admin_headers())
        trace = await t.json()
        names = [s["name"] for s in trace["spans"]]
        assert "first_token" in names and "decode" in names
        # 404-model path records a trace too (finished at 404)
        headers["X-Request-Id"] = "trace-missing-model"
        resp = await gw.client.post(
            "/v1/chat/completions",
            json={"model": "nope",
                  "messages": [{"role": "user", "content": "hi"}]},
            headers=headers,
        )
        assert resp.status == 404
        assert resp.headers["X-Request-Id"] == "trace-missing-model"
        t = await gw.client.get("/api/traces/trace-missing-model",
                                headers=await gw.admin_headers())
        assert (await t.json())["status"] == 404
    finally:
        await upstream.stop()
        await gw.close()


async def test_trace_completed_event_published():
    gw = await GatewayHarness.create()
    upstream = await MockOpenAIEndpoint(model="m1").start()
    try:
        gw.register_mock(upstream.url, ["m1"])
        sub_id, queue = gw.state.events.subscribe()
        try:
            headers = dict(await gw.inference_headers())
            headers["X-Request-Id"] = "trace-ev-1"
            resp = await gw.client.post(
                "/v1/chat/completions",
                json={"model": "m1",
                      "messages": [{"role": "user", "content": "hi"}]},
                headers=headers,
            )
            assert resp.status == 200
            await resp.read()
            event = None
            for _ in range(20):
                try:
                    candidate = queue.get_nowait()
                except asyncio.QueueEmpty:
                    await asyncio.sleep(0.01)
                    continue
                if candidate["type"] == "TraceCompleted":
                    event = candidate
                    break
            assert event is not None, "no TraceCompleted event seen"
            assert event["data"]["trace_id"] == "trace-ev-1"
            assert event["data"]["status"] == 200
        finally:
            gw.state.events.unsubscribe(sub_id)
    finally:
        await upstream.stop()
        await gw.close()


async def test_stream_trace_carries_token_timeline_and_goodput():
    """A streamed request's trace carries the sampled token timeline
    (first/last marks, max gap) and the gateway judges the request against
    its SLO targets — counters + goodput ratio visible in /metrics."""
    gw = await GatewayHarness.create()
    upstream = await MockOpenAIEndpoint(model="m1").start()
    try:
        gw.register_mock(upstream.url, ["m1"], name="ep-a")
        headers = dict(await gw.inference_headers())
        headers["X-Request-Id"] = "trace-timeline-1"
        resp = await gw.client.post(
            "/v1/chat/completions",
            json={"model": "m1", "stream": True,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers=headers,
        )
        assert resp.status == 200
        body = await resp.text()
        assert "[DONE]" in body

        t = await gw.client.get("/api/traces/trace-timeline-1",
                                headers=await gw.admin_headers())
        trace = await t.json()
        tl = trace.get("token_timeline")
        assert tl is not None, trace
        assert tl["chunks"] >= 1
        assert tl["marks_ms"] and tl["first_ms"] is not None
        assert tl["last_ms"] >= tl["first_ms"]
        assert tl["max_gap_ms"] >= 0.0
        assert tl["truncated"] is False

        # goodput: the mock upstream answers instantly, so the request met
        # its targets and the ledger says so
        m = await gw.client.get("/metrics")
        text = await m.text()
        assert 'llmlb_gateway_slo_eligible_total{model="m1"} 1' in text
        assert 'llmlb_gateway_slo_met_total{model="m1"} 1' in text
        assert 'llmlb_gateway_goodput_ratio{model="m1"} 1.0' in text

        # non-streaming requests are judged too (TTFT-only)
        resp = await gw.client.post(
            "/v1/chat/completions",
            json={"model": "m1",
                  "messages": [{"role": "user", "content": "hi"}]},
            headers=await gw.inference_headers(),
        )
        assert resp.status == 200
        await resp.read()
        text = await (await gw.client.get("/metrics")).text()
        assert 'llmlb_gateway_slo_eligible_total{model="m1"} 2' in text
    finally:
        await upstream.stop()
        await gw.close()


async def test_timeline_sampling_zero_disables_marks():
    gw = await GatewayHarness.create()
    upstream = await MockOpenAIEndpoint(model="m1").start()
    try:
        gw.register_mock(upstream.url, ["m1"])
        gw.state.traces.timeline_interval = 0  # operator disabled sampling
        headers = dict(await gw.inference_headers())
        headers["X-Request-Id"] = "trace-no-tl"
        resp = await gw.client.post(
            "/v1/chat/completions",
            json={"model": "m1", "stream": True,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers=headers,
        )
        assert resp.status == 200
        await resp.read()
        t = await gw.client.get("/api/traces/trace-no-tl",
                                headers=await gw.admin_headers())
        assert "token_timeline" not in await t.json()
    finally:
        await upstream.stop()
        await gw.close()


async def test_api_traces_endpoint_ring_wraparound():
    """/api/traces over HTTP with a tiny ring: older traces fall off, the
    buffered gauge tracks the ring size, and evicted ids 404."""
    gw = await GatewayHarness.create()
    upstream = await MockOpenAIEndpoint(model="m1").start()
    try:
        gw.register_mock(upstream.url, ["m1"])
        # shrink the ring in place (handlers read state.traces live)
        gw.state.traces = TraceStore(capacity=3)
        headers = dict(await gw.inference_headers())
        for i in range(5):
            headers["X-Request-Id"] = f"wrap-{i}"
            resp = await gw.client.post(
                "/v1/chat/completions",
                json={"model": "m1",
                      "messages": [{"role": "user", "content": "hi"}]},
                headers=headers,
            )
            assert resp.status == 200
            await resp.read()
        lst = await gw.client.get("/api/traces",
                                  headers=await gw.admin_headers())
        ids = [t["trace_id"] for t in (await lst.json())["traces"]]
        assert ids == ["wrap-4", "wrap-3", "wrap-2"]
        gone = await gw.client.get("/api/traces/wrap-0",
                                   headers=await gw.admin_headers())
        assert gone.status == 404
        kept = await gw.client.get("/api/traces/wrap-4",
                                   headers=await gw.admin_headers())
        assert (await kept.json())["status"] == 200
        text = await (await gw.client.get("/metrics")).text()
        assert "llmlb_gateway_traces_buffered 3" in text
    finally:
        await upstream.stop()
        await gw.close()


async def test_api_key_permission_for_traces():
    gw = await GatewayHarness.create()
    try:
        resp = await gw.client.post(
            "/api/api-keys",
            json={"name": "mr", "permissions": ["metrics.read"]},
            headers=await gw.admin_headers(),
        )
        assert resp.status == 201
        key = (await resp.json())["api_key"]
        ok = await gw.client.get(
            "/api/traces", headers={"Authorization": f"Bearer {key}"}
        )
        assert ok.status == 200
        resp = await gw.client.post(
            "/api/api-keys",
            json={"name": "inf", "permissions": ["openai.inference"]},
            headers=await gw.admin_headers(),
        )
        key2 = (await resp.json())["api_key"]
        denied = await gw.client.get(
            "/api/traces", headers={"Authorization": f"Bearer {key2}"}
        )
        assert denied.status == 403
    finally:
        await gw.close()
