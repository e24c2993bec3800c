"""Step-phase introspection, live MFU accounting, profiler capture, and the
TPU init-probe plumbing (PR 6 tentpole surfaces).

Covers: StepRecorder ring/anomaly/window semantics, the chip-spec and
FLOPs/bytes-per-token helpers, the CPU engine end to end (/api/steps,
phase histograms in /metrics, perf block in /api/system), the < 1%
instrumentation-overhead guarantee, and POST /api/profile producing a
non-empty downloadable trace on CPU JAX.
"""

import io
import time
import zipfile

import pytest

from llmlb_tpu.engine.stepstats import PHASES, StepRecorder
from llmlb_tpu.engine.telemetry import (
    chip_spec_for,
    model_bytes_per_token,
    model_flops_per_token,
)

# ------------------------------------------------------------- recorder units


def test_step_recorder_ring_wraparound():
    rec = StepRecorder(capacity=4)
    for i in range(10):
        rec.observe("decode", {"compute": 0.001}, tokens=1)
    snap = rec.snapshot(limit=10)
    assert snap["steps_total"] == 10
    assert snap["buffered"] == 4
    assert [r["seq"] for r in snap["records"]] == [10, 9, 8, 7]
    # limit caps below capacity too, newest first
    assert [r["seq"] for r in rec.snapshot(limit=2)["records"]] == [10, 9]


def test_step_recorder_flags_slow_steps_after_warmup():
    rec = StepRecorder(slow_floor_s=0.0)
    # warmup + baseline: 30 steps of ~1ms
    for _ in range(30):
        assert rec.observe("decode", {"compute": 0.001}) is False
    ema_before = rec.snapshot()["ema_step_s"]["decode"]
    # a 40x step flags...
    assert rec.observe("decode", {"compute": 0.040}) is True
    assert rec.slow_steps_total == 1
    # ...and must NOT drag the baseline up (else it masks the next one)
    assert rec.snapshot()["ema_step_s"]["decode"] == pytest.approx(
        ema_before
    )
    assert rec.observe("decode", {"compute": 0.040}) is True
    snap = rec.snapshot(slow_only=True)
    assert len(snap["records"]) == 2
    assert all(r["slow"] for r in snap["records"])
    # prefill has its own baseline: a first prefill step never flags
    assert rec.observe("prefill", {"compute": 0.5}) is False


def test_step_recorder_warmup_never_flags():
    rec = StepRecorder(slow_floor_s=0.0)
    flagged = [rec.observe("decode", {"compute": 0.001 * (i + 1)})
               for i in range(10)]
    assert not any(flagged)


def test_step_recorder_window_throughput_decode_only():
    rec = StepRecorder(window=4)
    rec.observe("prefill", {"compute": 1.0}, tokens=100)  # excluded
    for _ in range(6):  # window keeps the last 4
        rec.observe("decode", {"compute": 0.01, "fetch": 0.01}, tokens=8)
    busy, toks = rec.window_throughput()
    assert toks == 32
    assert busy == pytest.approx(4 * 0.02)
    assert StepRecorder().window_throughput() == (0.0, 0)


def test_step_recorder_snapshot_copies_records():
    rec = StepRecorder()
    rec.observe("decode", {"compute": 0.00123456789}, tokens=1)
    a = rec.snapshot()["records"][0]
    a["phases_s"]["compute"] = 999.0
    b = rec.snapshot()["records"][0]
    assert b["phases_s"]["compute"] != 999.0


# ---------------------------------------------------------- telemetry helpers


def test_chip_spec_lookup():
    assert chip_spec_for("TPU v5 lite").generation == "v5e"
    assert chip_spec_for("TPU v5p").generation == "v5p"
    assert chip_spec_for("TPU v4").generation == "v4"
    assert chip_spec_for("TPU v6 lite").generation == "v6e"
    assert chip_spec_for("cpu") is None
    assert chip_spec_for("unknown accelerator") is None


def test_model_cost_helpers():
    from llmlb_tpu.engine.presets import get_preset

    cfg = get_preset("debug-tiny")
    n_params = 1_000_000
    assert model_flops_per_token(cfg, n_params) == 2.0 * n_params
    # bytes: weights (amortized over batch) + KV reads for the context
    import jax.numpy as jnp

    itemsize = jnp.dtype(cfg.dtype).itemsize
    kv = cfg.num_layers * 64 * cfg.num_kv_heads * cfg.head_dim_ * 2 * itemsize
    assert model_bytes_per_token(cfg, n_params, 64, batch=1) == pytest.approx(
        n_params * itemsize + kv
    )
    assert model_bytes_per_token(cfg, n_params, 64, batch=4) == pytest.approx(
        n_params * itemsize / 4 + kv
    )
    # MoE: only routed experts count toward FLOPs
    moe = get_preset("debug-moe-tiny")
    dense_equiv = 2.0 * n_params
    assert model_flops_per_token(moe, n_params) < dense_equiv


# ------------------------------------------------------------------ e2e (CPU)


@pytest.fixture(scope="module")
def served_engine():
    from llmlb_tpu.engine.service import Engine

    engine = Engine.from_preset(
        "debug-tiny", num_slots=2, slot_capacity=64, prefill_buckets=(16,)
    )
    yield engine
    engine.shutdown()


async def _run_requests(engine, n=3, max_tokens=8):
    from llmlb_tpu.engine.scheduler import SamplingParams

    for i in range(n):
        await engine.complete(
            [1 + i, 2, 3, 4, 5],
            SamplingParams(temperature=0.0, max_tokens=max_tokens),
        )


async def test_engine_steps_endpoint_and_phase_metrics(served_engine):
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app

    engine = served_engine
    await _run_requests(engine)
    client = TestClient(TestServer(create_engine_app(engine,
                                                     owns_engine=False)))
    await client.start_server()
    try:
        resp = await client.get("/api/steps")
        assert resp.status == 200
        body = await resp.json()
        assert body["steps_total"] >= 3
        assert body["records"], body
        newest = body["records"][0]
        assert newest["kind"] in ("decode", "prefill")
        assert set(newest["phases_s"]) == set(PHASES)
        assert newest["total_s"] == pytest.approx(
            sum(newest["phases_s"].values()), abs=1e-5
        )
        kinds = {r["kind"] for r in body["records"]}
        assert "decode" in kinds and "prefill" in kinds
        # records are newest-first and sequences strictly decreasing
        seqs = [r["seq"] for r in body["records"]]
        assert seqs == sorted(seqs, reverse=True)
        assert "perf" in body and "ema_step_s" in body

        # limit + slow filters
        assert len((await (await client.get(
            "/api/steps?limit=2")).json())["records"]) == 2
        slow = await (await client.get("/api/steps?slow=1")).json()
        assert all(r["slow"] for r in slow["records"])
        assert (await client.get("/api/steps?limit=abc")).status == 400

        # /metrics carries the per-phase histograms with real samples
        text = await (await client.get("/metrics")).text()
        assert 'llmlb_engine_step_phase_seconds_count{phase="compute"}' in text
        compute_count = int(next(
            ln.rsplit(" ", 1)[1] for ln in text.splitlines()
            if ln.startswith(
                'llmlb_engine_step_phase_seconds_count{phase="compute"}')
        ))
        assert compute_count >= body["steps_total"] - 1
        assert "llmlb_engine_slow_steps_total" in text

        # CPU has no chip spec: perf block present, gauges absent
        system = await (await client.get("/api/system")).json()
        assert system["perf"]["available"] is False
        assert system["perf"]["flops_per_token"] > 0
        assert "llmlb_engine_mfu_ratio" not in text
    finally:
        await client.close()


async def test_instrumentation_overhead_under_one_percent(served_engine):
    """Acceptance: the full per-step recording path (StepRecorder.observe +
    EngineMetrics.record_step_phases) must cost < 1% of a measured engine
    step. Measured against the CPU debug engine's mean decode step — real
    TPU steps are orders of magnitude longer, so this is the conservative
    bound."""
    from llmlb_tpu.engine.metrics import EngineMetrics

    engine = served_engine
    await _run_requests(engine, n=2, max_tokens=16)
    hist = engine.core.metrics.decode_step
    assert hist.n > 0
    mean_step_s = hist.total / hist.n

    rec = StepRecorder()
    metrics = EngineMetrics()
    phases = {"plan": 1e-5, "host_sync": 1e-6, "dispatch": 1e-3,
              "compute": 1e-4, "fetch": 1e-4, "emit": 1e-4}
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        slow = rec.observe("decode", phases, active_slots=2, tokens=2)
        metrics.record_step_phases(phases, slow=slow)
    per_step = (time.perf_counter() - t0) / n
    # the timing side (10 perf_counter reads) is OS-cheap; bound the whole
    # record path against the measured mean step
    assert per_step < 0.01 * mean_step_s, (
        f"instrumentation {per_step * 1e6:.1f}µs/step vs mean step "
        f"{mean_step_s * 1e3:.3f}ms — over the 1% budget"
    )


# -------------------------------------------------------------- /api/profile


async def test_profile_capture_produces_downloadable_trace(tmp_path,
                                                           monkeypatch):
    """POST /api/profile start→stop on CPU JAX yields a completed capture
    whose zip artifact is non-empty and unpacks to real trace files."""
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine

    monkeypatch.setenv("LLMLB_TRACE_DIR", str(tmp_path))
    engine = Engine.from_preset(
        "debug-tiny", num_slots=2, slot_capacity=64, prefill_buckets=(16,)
    )
    client = TestClient(TestServer(create_engine_app(engine,
                                                     owns_engine=False)))
    await client.start_server()
    try:
        resp = await client.post("/api/profile",
                                 json={"action": "start", "seconds": 30})
        assert resp.status == 200
        started = await resp.json()
        capture_id = started["capture_id"]
        assert started["trace_dir"].startswith(str(tmp_path))

        # concurrent start refuses: the jax tracer is process-global
        dup = await client.post("/api/profile", json={"action": "start"})
        assert dup.status == 409

        status = await (await client.get("/api/profile")).json()
        assert status["recording"] is True

        # profile the serving loop itself so the trace has device events
        await _run_requests(engine, n=2)

        resp = await client.post("/api/profile", json={"action": "stop"})
        assert resp.status == 200
        done = await resp.json()
        assert done["capture_id"] == capture_id
        assert done["bytes"] > 0

        # double stop: nothing recording
        assert (await client.post(
            "/api/profile", json={"action": "stop"})).status == 409
        assert (await client.post(
            "/api/profile", json={"action": "nope"})).status == 400

        status = await (await client.get("/api/profile")).json()
        assert status["recording"] is False
        assert status["captures"][0]["capture_id"] == capture_id

        # the downloadable artifact: non-empty zip of real trace files
        art = await client.get(f"/api/profile/{capture_id}")
        assert art.status == 200
        assert art.headers["Content-Type"] == "application/zip"
        blob = await art.read()
        names = zipfile.ZipFile(io.BytesIO(blob)).namelist()
        assert names, "trace zip is empty"

        assert (await client.get("/api/profile/doesnotexist")).status == 404
    finally:
        await client.close()
        engine.shutdown()


async def test_profile_token_gates_every_route(monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine

    monkeypatch.setenv("LLMLB_PROFILE_TOKEN", "s3cret")
    engine = Engine.from_preset(
        "debug-tiny", num_slots=2, slot_capacity=64, prefill_buckets=(16,)
    )
    client = TestClient(TestServer(create_engine_app(engine,
                                                     owns_engine=False)))
    await client.start_server()
    try:
        assert (await client.post(
            "/api/profile", json={"action": "start"})).status == 401
        assert (await client.get("/api/profile")).status == 401
        assert (await client.get("/api/profile/x")).status == 401
        assert (await client.post("/debug/profile", json={})).status == 401
        ok = await client.get(
            "/api/profile", headers={"Authorization": "Bearer s3cret"}
        )
        assert ok.status == 200
    finally:
        await client.close()
        engine.shutdown()


def test_profile_wait_idle_wakes_on_stop_event_not_poll(tmp_path):
    """The /debug/profile wait path parks on the manager's idle event and
    wakes when the capture stops — the last 50 ms poll loop in a request
    path, now notify-based. Regression bound: wake latency well under one
    old poll tick."""
    import threading

    from llmlb_tpu.engine.profiling import ProfileManager

    mgr = ProfileManager(trace_root=str(tmp_path))
    assert mgr.wait_idle(0.01) is True  # idle from construction
    mgr.start(30)
    assert mgr.wait_idle(0.01) is False  # recording: the wait parks

    woke_after = {}

    def waiter():
        t0 = time.perf_counter()
        assert mgr.wait_idle(10.0) is True
        woke_after["s"] = time.perf_counter() - t0

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    t_stop = time.perf_counter()
    mgr.stop()
    t.join(timeout=5)
    stop_s = time.perf_counter() - t_stop
    assert not t.is_alive()
    # the waiter wakes with the stop itself, not a later poll tick; the
    # bound subtracts stop_trace's own serialization time
    assert woke_after["s"] - stop_s < 0.045, (
        f"wait_idle woke {woke_after['s'] * 1000:.1f}ms after a "
        f"{stop_s * 1000:.1f}ms stop — still polling?"
    )
