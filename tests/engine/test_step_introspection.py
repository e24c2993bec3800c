"""Step-phase introspection, live MFU accounting, profiler capture, and the
TPU init-probe plumbing (PR 6 tentpole surfaces).

Covers: StepRecorder ring/anomaly/window semantics, the chip-spec and
FLOPs/bytes-per-token helpers, the CPU engine end to end (/api/steps,
phase histograms in /metrics, perf block in /api/system), the < 1%
instrumentation-overhead guarantee, and POST /api/profile producing a
non-empty downloadable trace on CPU JAX.
"""

import asyncio
import io
import time
import zipfile

import pytest

from llmlb_tpu.engine import compilelog, stepstats
from llmlb_tpu.engine.stepstats import (
    GAP_BUCKETS,
    INFLIGHT_SPANS,
    LOOP_BUCKETS,
    PHASES,
    SPANS,
    LoopClock,
    StepRecorder,
)
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


# ------------------------------------------------ spans, loop clock (units)


def _stamped(monkeypatch, stamps):
    """A LoopClock whose perf_counter reads come from `stamps`, in order."""
    it = iter(stamps)
    monkeypatch.setattr(stepstats, "_now", lambda: next(it))
    rec = StepRecorder()  # reads the clock once, for its wall anchor
    return rec, LoopClock(rec, "main")


DECODE_STAMPS = [
    5.0,    # the recorder's wall anchor
    10.0,   # the clock is made: the loop is in `other`
    10.1,   # switch(admit)
    10.3,   # begin(host_sync): 0.2 s of admission, t0
    10.31,  # mark(dispatch)
    10.32,  # mark(compute)
    10.52,  # mark(fetch)
    10.54,  # mark(emit)
    10.55,  # close: t1
    10.56,  # resume: closing the record cost 0.01 s
    10.57,  # begin of the next step
    10.60,  # close
]


def _one_decode_step(monkeypatch):
    rec, clock = _stamped(monkeypatch, DECODE_STAMPS)
    clock.switch("admit")
    step = clock.begin("host_sync")
    for name in ("dispatch", "compute", "fetch", "emit"):
        step.mark(name)
    clock.close(step, "decode")
    rec.observe("decode", step.phases(), active_slots=2, tokens=16,
                request_ids={"0": "req-a"}, dispatches=1, span=step)
    clock.resume(step)
    return rec, clock


def test_a_step_record_is_a_measured_span(monkeypatch):
    rec, clock = _one_decode_step(monkeypatch)
    r = rec.snapshot()["records"][0]
    assert (r["t0_s"], r["t1_s"]) == (10.3, 10.55)
    assert r["wall_s"] == pytest.approx(0.25)
    assert [s[0] for s in r["spans"]] == [
        "host_sync", "dispatch", "compute", "fetch", "emit"]
    assert all(name in SPANS for name, _at, _dur in r["spans"])
    # spans lie end to end from t0: no hole, and they sum to the wall time
    at = 0.0
    for _name, start, dur in r["spans"]:
        assert start == pytest.approx(at, abs=1e-6)
        at += dur
    assert at == pytest.approx(r["wall_s"], abs=1e-6)
    assert r["since_prev"] == pytest.approx({
        "admit_s": 0.2, "control_s": 0.0, "record_s": 0.0, "idle_s": 0.0,
        "other_s": 0.1})
    assert set(r["since_prev"]) == {f"{b}_s" for b in GAP_BUCKETS}
    assert r["builds"] == {"count": 0, "names": []}
    assert r["loop"] == "main" and r["slow_in"] is None
    # ts is the last stamp through the recorder's one wall anchor
    assert r["ts"] == pytest.approx(rec._wall_anchor + 10.55)
    # the next record owns what closing this one cost, and no time is lost
    step = clock.begin("compute")
    clock.close(step, "decode")
    rec.observe("decode", step.phases(), span=step)
    nxt = rec.snapshot()["records"][0]
    assert nxt["since_prev"]["record_s"] == pytest.approx(0.01)
    assert r["t1_s"] + sum(nxt["since_prev"].values()) == pytest.approx(
        nxt["t0_s"], abs=50e-6)
    # the cumulative buckets hold every second since the clock was made
    assert set(clock.acc) == set(LOOP_BUCKETS)
    assert sum(clock.acc.values()) == pytest.approx(10.60 - 10.0)
    assert clock.acc["step"] == pytest.approx(0.25 + 0.03)


def test_legacy_fields_equal_what_observe_produced_for_the_same_stamps(
        monkeypatch):
    """The arithmetic the six step paths did by hand (t_sync, t_dispatch,
    ... and the plan time lumped in) against the span helper, stamp for
    stamp: every field a record had before PR 24 reads the same."""
    rec, _clock = _one_decode_step(monkeypatch)
    new = rec.snapshot()["records"][0]
    old_rec = StepRecorder()
    by_hand = {"plan": 10.3 - 10.1, "draft": 0.0, "host_sync": 10.31 - 10.3,
               "dispatch": 10.32 - 10.31, "compute": 10.52 - 10.32,
               "fetch": 10.54 - 10.52, "emit": 10.55 - 10.54}
    old_rec.observe("decode", by_hand, active_slots=2, tokens=16,
                    request_ids={"0": "req-a"}, dispatches=1)
    old = old_rec.snapshot()["records"][0]
    legacy = ("seq", "kind", "total_s", "phases_s", "active_slots", "tokens",
              "dispatches", "request_ids", "slow")
    for key in legacy:
        assert new[key] == pytest.approx(old[key]), key
    assert new["total_s"] == pytest.approx(sum(new["phases_s"].values()))
    # a record made without a span (the unit tests' way) is whole too
    assert old["wall_s"] == pytest.approx(0.25)
    assert [s[0] for s in old["spans"]] == [
        "host_sync", "dispatch", "compute", "fetch", "emit"]
    assert old["since_prev"]["admit_s"] == pytest.approx(0.2)


def test_activate_is_a_span_of_its_own_and_still_part_of_legacy_emit(
        monkeypatch):
    rec, clock = _stamped(monkeypatch, [
        1.0, 2.0, 2.0, 2.01, 2.11, 2.115, 2.140, 2.141, 2.15, 2.2, 2.3, 2.31])
    step = clock.begin("dispatch")       # 2.0
    step.mark("compute")                 # 2.01
    step.mark("emit")                    # 2.11
    clock.mark("activate")               # 2.115, from inside _activate_group
    step.mark("emit")                    # 2.140
    clock.close(step, "prefill")         # 2.141
    phases = step.phases()
    assert phases["emit"] == pytest.approx(0.031)   # delivery + activation
    rec.observe("prefill", phases, span=step)
    r = rec.snapshot()["records"][0]
    emit = sum(d for n, _a, d in r["spans"] if n == "emit")
    activate = sum(d for n, _a, d in r["spans"] if n == "activate")
    assert activate == pytest.approx(0.025)
    assert emit == pytest.approx(r["phases_s"]["emit"] - activate)
    # between steps the same call is no span: the loop's bucket holds it
    clock.resume(step)                   # 2.15
    clock.mark("activate")               # no clock read
    # a frozen step (the context-parallel prefill) keeps later spans out of
    # the legacy phases and in the record
    step = clock.begin("dispatch")       # 2.2
    step.freeze_phases()
    step.mark("activate")                # 2.3
    clock.close(step, "prefill")         # 2.31
    assert step.phases() == pytest.approx({"plan": 0.0, "dispatch": 0.1})
    assert [n for n, _a, _d in step.spans] == ["dispatch", "activate"]


def test_a_handover_closes_a_step_where_the_next_begins(monkeypatch):
    """A burst that leaves before its predecessor is recorded
    (LoopClock.handover): the two records do not overlap, no time lies
    between them, the second knows its seq before the first is observed, and
    host work with a burst in flight is `compute` in the legacy phases, a
    span of its own name in `spans` and CPU in `host_cpu_s`."""
    cpu = iter([0.0, 0.0,            # the clock is made; begin
                0.03, 0.03,          # into and out of the first `compute`
                0.04, 0.04,          # handover: close, begin
                0.08, 0.08,          # around the second `compute`
                0.10])               # close
    monkeypatch.setattr(stepstats, "_cpu", lambda: next(cpu))
    rec, clock = _stamped(monkeypatch, [
        1.0, 2.0,  # the recorder's anchor; the clock is made
        2.0,    # begin(host_sync): t0
        2.01,   # mark(dispatch)
        2.02,   # mark(host_sync_inflight)
        2.03,   # mark(compute)
        2.13,   # mark(fetch)
        2.14,   # handover: the first step's t1 and the second's t0
        2.15,   # mark(emit_inflight)
        2.17,   # mark(host_sync_inflight)
        2.18,   # mark(compute)
        2.28,   # mark(fetch)
        2.29,   # mark(emit)
        2.30,   # close
        2.31,   # resume
    ])
    first = clock.begin("host_sync")
    for name in ("dispatch", "host_sync_inflight", "compute", "fetch"):
        first.mark(name)
    second = clock.handover(first, "decode", "dispatch")
    assert (first.t1, second.t0, second.seq) == (2.14, 2.14, first.seq + 1)
    second.mark("emit_inflight")
    # the first record is observed inside the second step
    rec.observe("decode", first.phases(), span=first)
    for name in ("host_sync_inflight", "compute", "fetch", "emit"):
        second.mark(name)
    clock.close(second, "decode")
    rec.observe("decode", second.phases(), span=second)
    clock.resume(second)
    b, a = rec.snapshot()["records"]
    assert (a["seq"], b["seq"]) == (first.seq, second.seq)
    assert a["t1_s"] == b["t0_s"] == 2.14
    assert b["since_prev"] == pytest.approx(dict.fromkeys(
        (f"{g}_s" for g in GAP_BUCKETS), 0.0))
    assert [n for n, _a, _d in b["spans"]] == [
        "dispatch", "emit_inflight", "host_sync_inflight", "compute",
        "fetch", "emit"]
    assert all(n in SPANS for n, _a, _d in a["spans"] + b["spans"])
    assert a["phases_s"] == pytest.approx({
        "plan": 0.0, "draft": 0.0, "host_sync": 0.01, "dispatch": 0.01,
        "compute": 0.01 + 0.10, "fetch": 0.01, "emit": 0.0})
    assert b["phases_s"] == pytest.approx({
        "plan": 0.0, "draft": 0.0, "host_sync": 0.0, "dispatch": 0.01,
        "compute": 0.02 + 0.01 + 0.10, "fetch": 0.01, "emit": 0.01})
    for r in (a, b):
        assert r["total_s"] == pytest.approx(r["wall_s"])
    # the thread's CPU outside `compute`, the in-flight spans included
    assert a["host_cpu_s"] == pytest.approx(0.03 + 0.01)
    assert b["host_cpu_s"] == pytest.approx(0.04 + 0.02)
    assert clock.acc["step"] == pytest.approx(0.30)
    assert clock.acc["record"] == pytest.approx(0.01)


def test_a_prefill_dispatched_ahead_lies_between_two_bursts(monkeypatch):
    """Admission ahead (scheduler._admit_ahead): the fetched burst's step is
    closed, the arrival is placed in `admit`, its prefill's step begins
    behind the closed one (LoopClock.begin(after=)) and is handed over to
    the burst behind it. The three records tile, their seqs follow each
    other before any is observed, and the in-flight spans of the prefill
    and of the burst are `compute` in the legacy phases."""
    rec, clock = _stamped(monkeypatch, [
        1.0, 2.0,  # the recorder's anchor; the clock is made
        2.0,    # begin(dispatch): the burst that will be fetched, t0
        2.01,   # mark(compute)
        2.11,   # mark(fetch)
        2.12,   # close: its t1 — an arrival can be placed
        2.12,   # switch(admit)
        2.125,  # begin(dispatch, after=): 5 ms of placing; the prefill's t0
        2.130,  # mark(activate_inflight), from inside _activate_group
        2.135,  # handover: the prefill's t1 and the next burst's t0
        2.137,  # mark(emit_inflight)
        2.140,  # mark(host_sync_inflight)
        2.142,  # mark(compute)
        2.242,  # mark(fetch)
        2.243,  # mark(emit)
        2.245,  # close
        2.246,  # resume
    ])
    fetched = clock.begin("dispatch")
    fetched.mark("compute")
    fetched.mark("fetch")
    clock.close(fetched, "decode")
    clock.switch("admit")
    prefill = clock.begin("dispatch", after=fetched)
    clock.mark("activate_inflight")
    behind = clock.handover(prefill, "prefill", "dispatch_inflight")
    assert (prefill.seq, behind.seq) == (fetched.seq + 1, fetched.seq + 2)
    behind.mark("emit_inflight")
    # both closed records are observed inside the burst behind them
    rec.observe("decode", fetched.phases(), span=fetched)
    rec.observe("prefill", prefill.phases(), span=prefill)
    for name in ("host_sync_inflight", "compute", "fetch", "emit"):
        behind.mark(name)
    clock.close(behind, "decode")
    rec.observe("decode", behind.phases(), span=behind)
    clock.resume(behind)
    c, b, a = rec.snapshot()["records"]
    assert [r["seq"] for r in (a, b, c)] == [fetched.seq, prefill.seq,
                                             behind.seq]
    assert (a["t1_s"], b["t0_s"], b["t1_s"], c["t0_s"]) == (
        2.12, 2.125, 2.135, 2.135)
    assert b["since_prev"] == pytest.approx({
        "admit_s": 0.005, "control_s": 0.0, "record_s": 0.0, "idle_s": 0.0,
        "other_s": 0.0})
    assert sum(c["since_prev"].values()) == 0.0
    assert [n for n, _a, _d in b["spans"]] == ["dispatch",
                                               "activate_inflight"]
    assert [n for n, _a, _d in c["spans"]] == [
        "dispatch_inflight", "emit_inflight", "host_sync_inflight",
        "compute", "fetch", "emit"]
    assert all(n in SPANS for r in (a, b, c) for n, _a, _d in r["spans"])
    assert {"activate_inflight", "dispatch_inflight"} <= set(INFLIGHT_SPANS)
    # the legacy-phases rule: the placing is the prefill's plan, its
    # activation behind the dispatch is compute (the prefill computes), and
    # so is the call of the burst behind both
    assert b["phases_s"] == pytest.approx({
        "plan": 0.005, "draft": 0.0, "host_sync": 0.0, "dispatch": 0.005,
        "compute": 0.005, "fetch": 0.0, "emit": 0.0})
    assert c["phases_s"] == pytest.approx({
        "plan": 0.0, "draft": 0.0, "host_sync": 0.0, "dispatch": 0.0,
        "compute": 0.002 + 0.003 + 0.002 + 0.100, "fetch": 0.001,
        "emit": 0.002})
    assert b["total_s"] == pytest.approx(b["wall_s"] + 0.005)
    # the loop resumes where the first burst's step was opened from
    assert clock._bucket == "other"
    assert clock.acc["admit"] == pytest.approx(0.005)
    assert clock.acc["step"] == pytest.approx(0.12 + 0.01 + 0.11)


def test_an_abandoned_step_leaves_no_record_and_loses_no_time(monkeypatch):
    rec, clock = _stamped(monkeypatch, [1.0, 2.0, 2.5, 2.7, 3.0, 3.1, 3.2])
    clock.switch("admit")                # 2.5
    clock.begin("host_sync")             # 2.7
    clock.abandon()                      # 3.0: nothing to dispatch
    assert rec.seq == 0
    step = clock.begin("host_sync")      # 3.1
    assert step.since_prev == pytest.approx({
        "admit": 0.2, "control": 0.0, "record": 0.0, "idle": 0.0,
        "other": 0.5 + 0.3 + 0.1})
    clock.abandon()


def test_a_stall_between_steps_is_flagged_and_named():
    """The detector judges the time since the previous record ended, idle
    sleep left out: a stall that no span covers is slow all the same."""
    rec = StepRecorder(slow_floor_s=0.0)
    clock = LoopClock(rec, "main")

    def step_after(bucket, seconds, idle=0.0):
        step = clock.begin("compute")
        step.since_prev = dict.fromkeys(GAP_BUCKETS, 0.0)
        step.since_prev[bucket] = seconds
        step.since_prev["idle"] = idle
        clock.close(step, "decode")
        step.t1 = step.t0 + 0.001
        step.spans = [("compute", step.t0, 0.001)]
        slow = rec.observe("decode", step.phases(), span=step)
        clock.resume(step)
        return slow

    for _ in range(30):
        assert step_after("other", 0.0001) is False
    # a long sleep with nothing to do is no stall
    assert step_after("other", 0.0001, idle=5.0) is False
    assert step_after("other", 0.060) is True
    assert rec.snapshot(slow_only=True)["records"][0]["slow_in"] == "other_s"
    assert step_after("admit", 0.060) is True
    assert rec.snapshot(slow_only=True)["records"][0]["slow_in"] == "admit_s"


# ------------------------------------------------------ the program ledger


def test_ledger_counts_a_program_per_new_shape_and_names_its_step():
    import jax
    import numpy as np

    @jax.jit
    def ledger_probe(x):
        return x * 2 + 1

    base = compilelog.counters()
    ledger_probe(np.ones((3, 5), np.float32)).block_until_ready()
    first = compilelog.summary(base)
    assert first["programs_total"] == 1
    assert first["by_thread"]["other"]["programs_total"] == 1
    assert all(first["seconds_total"][s] > 0 for s in compilelog.STAGES)
    # the same shape again builds nothing
    ledger_probe(np.ones((3, 5), np.float32)).block_until_ready()
    assert compilelog.summary(base)["programs_total"] == 1
    # a new shape inside a step is named on that step and carries its seq
    compilelog.enter_step(77)
    ledger_probe(np.ones((4, 5), np.float32)).block_until_ready()
    assert compilelog.leave_step() == ["jit(ledger_probe)"]
    after = compilelog.summary(base)
    assert after["programs_total"] == 2
    assert after["repeat_builds_total"] == 1  # the same name, built again
    newest, older = compilelog.recent(2, base)
    assert (newest["fun_name"], newest["step_seq"]) == (
        "jit(ledger_probe)", 77)
    assert older["step_seq"] is None and newest["thread"] == "other"
    assert newest["trace_s"] > 0 and newest["backend_s"] > 0
    # outside a step again
    assert compilelog.leave_step() == []
    assert compilelog.recent(0, base) == []


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


def _oldest_first(core, limit=512):
    return core.step_stats.snapshot(limit=limit)["records"][::-1]


def _span_sum(record, name):
    return sum(dur for n, _at, dur in record["spans"] if n == name)


async def test_served_records_are_spans_in_the_order_run(served_engine):
    """A one-shot prefill, the chunks of a long prompt and the decode steps
    of a served engine: span order and offsets, `activate` where a request
    enters decode and nowhere else, and `emit` in the spans shorter than the
    legacy emit phase by exactly the activation."""
    from llmlb_tpu.engine.scheduler import SamplingParams

    engine = served_engine
    start = engine.core.step_stats.seq
    await _run_requests(engine, n=1)
    # 40 tokens against a largest bucket of 16: three chunks, the last
    # one activates
    await engine.complete(list(range(1, 41)),
                          SamplingParams(temperature=0.0, max_tokens=4))
    records = [r for r in _oldest_first(engine.core) if r["seq"] > start]
    prefill = [r for r in records if r["kind"] == "prefill"]
    decode = [r for r in records if r["kind"] == "decode"]
    assert len(prefill) == 4 and decode
    for r in records:
        names = [n for n, _at, _dur in r["spans"]]
        assert set(names) <= set(SPANS)
        at = 0.0
        for _name, offset, dur in r["spans"]:
            assert offset == pytest.approx(at, abs=2e-6)
            at += dur
        # no hole inside a step: the spans cover its wall time
        assert at >= 0.98 * r["wall_s"] - 2e-6
        assert r["wall_s"] == pytest.approx(r["t1_s"] - r["t0_s"], abs=2e-6)
        assert r["total_s"] == pytest.approx(
            r["wall_s"] + r["since_prev"]["admit_s"], abs=1e-5)
        assert r["phases_s"]["plan"] == pytest.approx(
            r["since_prev"]["admit_s"], abs=2e-6)
    by_seq = {r["seq"]: r for r in records}
    for r, nxt in zip(decode, decode[1:] + [None]):
        # three of the five orders of a decode cycle (docs/scheduling.md;
        # a slot is free all along here, so no burst is queued behind the
        # one in flight: tests/engine/test_decode_overlap.py): today's,
        # the one of a burst that left before its predecessor was emitted,
        # and the one of a burst behind a prefill that did; a burst whose
        # successor left ahead has no `emit` of its own, the successor's
        # `emit_inflight` holds it
        names = [n for n, _a, _d in r["spans"]]
        before = by_seq.get(r["seq"] - 1)
        behind_a_prefill = bool(before and before["kind"] == "prefill"
                                and before.get("dispatched_ahead"))
        head = (["dispatch_inflight", "emit_inflight"] if behind_a_prefill
                else ["dispatch", "emit_inflight"] if r["dispatched_ahead"]
                else ["host_sync", "dispatch"])
        assert not behind_a_prefill or r["dispatched_ahead"]
        followed = nxt is not None and nxt["dispatched_ahead"]
        assert names == head + ["host_sync_inflight", "compute", "fetch"] + (
            [] if followed else ["emit"])
        assert (r["ahead_blocked_by"] is None) == r["dispatched_ahead"]
        assert not r["queued_behind"]
        if followed:
            assert nxt["seq"] == r["seq"] + 1
            assert nxt["since_prev"]["record_s"] == 0.0
        # legacy phases: host work with a burst in flight is `compute`, the
        # interval from the dispatch's return to the device's completion
        assert _span_sum(r, "emit") == pytest.approx(r["phases_s"]["emit"],
                                                     abs=2e-6)
        assert r["phases_s"]["compute"] == pytest.approx(
            sum(_span_sum(r, n) for n in ("compute",) + INFLIGHT_SPANS),
            abs=3e-6)
        assert r["phases_s"]["host_sync"] == pytest.approx(
            _span_sum(r, "host_sync"), abs=2e-6)
    assert any(r["dispatched_ahead"] for r in decode)
    assert not decode[0]["dispatched_ahead"]
    activating = [r for r in prefill if _span_sum(r, "activate") > 0]
    assert [r["seq"] for r in activating] == [prefill[0]["seq"],
                                              prefill[3]["seq"]]
    for r in prefill:
        names = [n for n, _a, _d in r["spans"]]
        assert names[:3] == ["dispatch", "compute", "emit"]
        assert (names[3:] == ["activate"]) == (r in activating)
        assert _span_sum(r, "emit") == pytest.approx(
            r["phases_s"]["emit"] - _span_sum(r, "activate"), abs=3e-6)
        assert _span_sum(r, "emit") < r["phases_s"]["emit"] or \
            r not in activating
        # a one-shot group says which order it took; a chunk has none
        assert r.get("dispatched_ahead") == (
            False if r is prefill[0] else None)


async def test_a_served_arrival_is_prefilled_ahead_under_inflight_spans(
        served_engine, monkeypatch):
    """A request that comes while another decodes and a slot is free: its
    prefill leaves ahead, and the span names of the prefill and of the
    burst behind it follow the legacy-phases rule — what the host does with
    the prefill on the device is `compute`, not `emit` or `dispatch`. (An
    engine with no mixed step: with one, a lone short arrival has no
    prefill record at all, tests/engine/test_mixed_admission.py.)"""
    from llmlb_tpu.engine.scheduler import Request, SamplingParams
    from tests.support import collect

    engine = served_engine
    core = engine.core
    monkeypatch.setattr(core, "mixed_width", 0)
    start = core.step_stats.seq
    ahead_before = core.metrics.summary()["prefills_dispatched_ahead_total"]
    # the second request is submitted from the loop's own thread while the
    # first one's first burst is in flight (_prepare_burst runs there), so
    # that the fetch behind it finds it whatever this machine's load
    late_request = Request(prompt_ids=[8, 2, 3, 4, 5], sampling=SamplingParams(
        temperature=0.0, max_tokens=8))
    prepare, pending = core._prepare_burst, [late_request]

    def prepare_and_submit(rows, k):
        if pending:
            core.submit(pending.pop())
        return prepare(rows, k)

    core._prepare_burst = prepare_and_submit
    try:
        await engine.complete([9, 2, 3, 4, 5], SamplingParams(
            temperature=0.0, max_tokens=56))
        assert len(collect(late_request)[0]) == 8
    finally:
        core._prepare_burst = prepare
    records = [r for r in _oldest_first(core) if r["seq"] > start]
    late = [r for r in records if r["kind"] == "prefill"][1]
    assert late["dispatched_ahead"]
    assert core.metrics.summary()["prefills_dispatched_ahead_total"] == \
        ahead_before + 1
    behind = next(r for r in records if r["seq"] == late["seq"] + 1)
    fetched = next(r for r in records if r["seq"] == late["seq"] - 1)
    assert behind["kind"] == fetched["kind"] == "decode"
    assert behind["dispatched_ahead"] and behind["active_slots"] == 2
    assert [n for n, _a, _d in late["spans"]] == ["dispatch",
                                                  "activate_inflight"]
    assert [n for n, _a, _d in behind["spans"]][:2] == [
        "dispatch_inflight", "emit_inflight"]
    assert [n for n, _a, _d in fetched["spans"]][-1] == "fetch"
    assert set(n for r in records for n, _a, _d in r["spans"]) <= set(SPANS)
    # legacy phases: no emit and no activate on the prefill's record, its
    # in-flight activation under compute; no dispatch on the burst's
    assert late["phases_s"]["emit"] == 0.0
    assert late["phases_s"]["compute"] == pytest.approx(
        _span_sum(late, "activate_inflight"), abs=2e-6)
    assert late["phases_s"]["plan"] == pytest.approx(
        late["since_prev"]["admit_s"], abs=2e-6)
    assert late["since_prev"]["admit_s"] > 0
    assert behind["phases_s"]["dispatch"] == 0.0
    assert behind["phases_s"]["compute"] == pytest.approx(
        sum(_span_sum(behind, n) for n in ("compute",) + INFLIGHT_SPANS),
        abs=3e-6)
    # the three records end and begin at one stamp each, less the placing
    assert late["t0_s"] - fetched["t1_s"] == pytest.approx(
        sum(late["since_prev"].values()), abs=5e-6)
    assert behind["t0_s"] == late["t1_s"]


async def test_no_time_is_lost_between_consecutive_records(served_engine):
    engine = served_engine
    await _run_requests(engine, n=3, max_tokens=24)
    records = _oldest_first(engine.core)
    assert len(records) > 50
    for prev, cur in zip(records[-51:], records[-50:]):
        assert cur["seq"] == prev["seq"] + 1 and cur["loop"] == "main"
        assert prev["t1_s"] + sum(cur["since_prev"].values()) == \
            pytest.approx(cur["t0_s"], abs=50e-6)
        # closing a record costs something, and the next one says what:
        # in its gap, or, where it left ahead, in its `emit_inflight` (a
        # prefill that left ahead: in that of the burst behind it)
        if cur.get("dispatched_ahead") and cur["kind"] == "decode":
            assert cur["since_prev"]["record_s"] == 0.0
            assert cur["t0_s"] == prev["t1_s"]  # one clock read
            assert _span_sum(cur, "emit_inflight") > 0
        elif not cur.get("dispatched_ahead"):
            assert cur["since_prev"]["record_s"] > 0


async def test_loop_buckets_sum_to_the_interval(served_engine):
    """Across an idle second and a busy one, every second of the loop
    thread's life is in exactly one bucket of loop_seconds_total."""
    engine = served_engine
    metrics = engine.core.metrics

    def reading():
        return time.perf_counter(), metrics.summary()["loop_seconds_total"]

    t_a, a = reading()
    time.sleep(1.0)
    t_b, b = reading()
    await _run_requests(engine, n=6, max_tokens=50)
    t_c, c = reading()
    assert set(a) == {"main"} and set(a["main"]) == set(LOOP_BUCKETS)
    for (t0, x), (t1, y) in (((t_a, a), (t_b, b)), ((t_b, b), (t_c, c))):
        delta = {k: y["main"][k] - x["main"][k] for k in LOOP_BUCKETS}
        assert all(v >= -1e-6 for v in delta.values()), delta
        assert sum(delta.values()) == pytest.approx(t1 - t0, rel=0.01), delta
    idle = {k: b["main"][k] - a["main"][k] for k in LOOP_BUCKETS}
    busy = {k: c["main"][k] - b["main"][k] for k in LOOP_BUCKETS}
    assert idle["idle"] > 0.8 and idle["step"] == 0.0
    assert busy["step"] > busy["idle"]
    # whatever no step and no named bucket holds stays small
    assert busy["other"] < 0.1 * (sum(busy.values()) - busy["idle"])


async def test_a_stall_in_admission_is_flagged_slow_and_named(served_engine):
    """A stall that falls outside every span (here: _drain_pending asleep
    for 60 ms between two decode steps) raises a slow record that names the
    bucket, lands in /api/steps?slow=1 and in its victims' flight records."""
    from llmlb_tpu.engine.scheduler import SamplingParams

    engine = served_engine
    core = engine.core
    # arm the detector: its baseline starts at the first step, which built
    # the decode program, and comes down by a twentieth a step
    for _ in range(20):
        await _run_requests(engine, n=1, max_tokens=40)
        if core.step_stats.snapshot(limit=0)["ema_step_s"]["decode"] < 0.010:
            break
    before = core.step_stats.slow_steps_total
    plain = core._drain_pending
    stall = {"left": 0}

    def drain_with_a_stall():
        if stall["left"] and any(s.request is not None and not s.prefilling
                                 for s in core.slots):
            stall["left"] -= 1
            time.sleep(0.060)
        plain()

    core._drain_pending = drain_with_a_stall
    try:
        # three requests on two slots: while one waits in the queue every
        # decode cycle goes through admission (a steady batch with nothing
        # waiting dispatches its bursts ahead and never gets there)
        tasks = [asyncio.ensure_future(engine.complete(
            [7 + i, 2, 3, 4, 5],
            SamplingParams(temperature=0.0, max_tokens=40)))
            for i in range(3)]
        await asyncio.sleep(0.02)
        stall["left"] = 1
        await asyncio.gather(*tasks)
    finally:
        core._drain_pending = plain
    assert core.step_stats.slow_steps_total > before
    slow = [r for r in core.step_stats.snapshot(
        limit=512, slow_only=True)["records"]
        if r["since_prev"]["admit_s"] >= 0.055]
    assert slow, "the stalled step was not flagged"
    record = slow[0]
    assert record["kind"] == "decode" and record["slow_in"] == "admit_s"
    assert record["wall_s"] < 0.055  # the step itself was not slow
    assert record["request_ids"]
    rid = next(iter(record["request_ids"].values()))
    events = core.flightrec.timeline(rid)["events"]
    flagged = [e for e in events if e["event"] == "slow_step"]
    assert flagged and flagged[-1]["attrs"]["slow_in"] == "admit_s"
    assert flagged[-1]["attrs"]["step_seq"] == record["seq"]


async def test_verify_records_carry_a_draft_span():
    from llmlb_tpu.engine.scheduler import SamplingParams
    from llmlb_tpu.engine.service import Engine

    engine = Engine.from_preset(
        "debug-tiny", spec_decode=True, num_slots=2, slot_capacity=256,
        prefill_buckets=(16, 32, 64))
    try:
        ids = engine.encode_chat([{"role": "user", "content":
                                   "count: 1 2 3 4 5 6 7 8 9 then repeat: "
                                   "1 2 3 4 5"}])
        await engine.complete(
            ids, SamplingParams(temperature=0.0, max_tokens=60))
        records = _oldest_first(engine.core)
    finally:
        engine.shutdown()
    verify = [r for r in records if r["kind"] == "verify"]
    assert verify
    for r in verify:
        assert [n for n, _a, _d in r["spans"]] == [
            "draft", "host_sync", "dispatch", "compute", "fetch", "emit"]
        assert _span_sum(r, "draft") == pytest.approx(
            r["phases_s"]["draft"], abs=2e-6)
        assert sum(d for _n, _a, d in r["spans"]) >= 0.98 * r["wall_s"] - 2e-6
    # a drafting step that found no draft is a decode record with the span
    for r in records:
        if r["kind"] == "decode" and r["phases_s"]["draft"] > 0:
            assert r["spans"][0][0] == "draft"
    for prev, cur in zip(records, records[1:]):
        assert prev["t1_s"] + sum(cur["since_prev"].values()) == \
            pytest.approx(cur["t0_s"], abs=50e-6)


async def test_steps_health_and_metrics_serve_the_ledger_and_the_buckets(
        served_engine):
    from aiohttp.test_utils import TestClient, TestServer

    from llmlb_tpu.engine.server import create_engine_app

    engine = served_engine
    await _run_requests(engine, n=1)
    client = TestClient(TestServer(create_engine_app(engine,
                                                     owns_engine=False)))
    await client.start_server()
    try:
        body = await (await client.get("/api/steps?limit=512")).json()
        ledger = body["compile"]
        assert ledger["programs_total"] >= 1
        assert set(ledger["seconds_total"]) == set(compilelog.STAGES)
        assert set(ledger["by_thread"]) == set(compilelog.THREAD_CLASSES)
        assert 1 <= len(ledger["builds"]) <= 64
        # a build made inside a step carries that step's seq, and the
        # step's record names the program
        by_seq = {r["seq"]: r for r in body["records"]}
        for b in ledger["builds"]:
            assert set(b) >= {"ts", "fun_name", "thread", "trace_s",
                              "lower_s", "backend_s", "cache_hit", "step_seq"}
            # (the ledger is the process's: another test's engine may
            # have built under the same seq, at another time)
            r = by_seq.get(b["step_seq"])
            if r and r["ts"] - r["wall_s"] <= b["ts"] <= r["ts"]:
                assert b["thread"] == "loop"
                assert b["fun_name"] in r["builds"]["names"]
        health = await (await client.get("/api/health")).json()
        served = health["metrics"]
        assert served["compile"]["programs_total"] == ledger["programs_total"]
        assert "builds" not in served["compile"]
        assert set(served["loop_seconds_total"]["main"]) == set(LOOP_BUCKETS)
        text = await (await client.get("/metrics")).text()
        for series in (
                'llmlb_engine_loop_seconds_total{loop="main",bucket="step"}',
                'llmlb_engine_loop_seconds_total{loop="main",bucket="idle"}',
                'llmlb_engine_programs_built_total{thread="loop"}',
                'llmlb_engine_compile_seconds_total{stage="backend"}'):
            assert series in text, series
    finally:
        await client.close()


async def test_a_capture_shows_the_steps_joined_to_the_records_by_seq(
        served_engine, tmp_path):
    """Every step is a `llmlb.step` event on the host plane of a capture,
    with its spans inside it, and carries the seq of its /api/steps
    record: joined by that, and by no clock arithmetic."""
    import glob
    import os

    import jax

    from llmlb_tpu.engine.profiling import ProfileManager

    engine = served_engine
    mgr = ProfileManager(trace_root=str(tmp_path))
    mgr.start(30)
    first = engine.core.step_stats.seq + 1
    await _run_requests(engine, n=1)
    last = engine.core.step_stats.seq
    done = mgr.stop()
    found = glob.glob(os.path.join(done["trace_dir"], "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    profile = jax.profiler.ProfileData.from_file(found[0])
    steps, spans = {}, set()
    for plane in profile.planes:
        if not (plane.name or "").startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name == "llmlb.step":
                    stats = dict(event.stats)
                    steps[int(stats["seq"])] = stats["kind"]
                elif event.name in SPANS:
                    spans.add(event.name)
    records = {r["seq"]: r["kind"] for r in _oldest_first(engine.core)
               if first <= r["seq"] <= last}
    assert records and {s: k for s, k in steps.items()
                        if first <= s <= last} == records
    assert {"dispatch", "compute", "emit", "activate"} <= spans


async def test_instrumentation_overhead_under_one_percent(served_engine):
    """Acceptance: the full per-step recording path (the loop clock's
    switches, the span helper, StepRecorder.observe +
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

    # the whole path a decode step takes: the loop's two bucket switches,
    # the step's five spans (a clock read and an inactive TraceMe each), the
    # record, the histograms, and back to the loop's bucket
    rec = StepRecorder()
    clock = LoopClock(rec, "main")
    metrics = EngineMetrics()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        clock.switch("admit")
        clock.switch("other")
        step = clock.begin("host_sync")
        for name in ("dispatch", "compute", "fetch", "emit"):
            step.mark(name)
        clock.close(step, "decode")
        phases = step.phases()
        slow = rec.observe("decode", phases, active_slots=2, tokens=2,
                           request_ids={"0": "a", "1": "b"}, span=step)
        metrics.record_step_phases(phases, slow=slow)
        clock.resume(step)
    per_step = (time.perf_counter() - t0) / n
    assert len(rec.snapshot(limit=1)["records"][0]["spans"]) == 5
    # the timing side (9 perf_counter reads) is OS-cheap; bound the whole
    # record path against the measured mean step
    assert per_step < 0.01 * mean_step_s, (
        f"instrumentation {per_step * 1e6:.1f}µs/step vs mean step "
        f"{mean_step_s * 1e3:.3f}ms — over the 1% budget"
    )


async def test_a_prefill_steps_counters_mark_and_cut_are_inside_the_guarantee(
        served_engine):
    """The same bound for what a PREFILL step records since the `counters`
    span and the cut of the `prefill` stage (docs/tracing.md "A request's
    way in"): five marks, the cut opened at the step's begin and closed at
    the activation's stamp, one more float add at the close — against the
    CPU debug engine's mean prefill step."""
    from llmlb_tpu.engine.metrics import EngineMetrics

    engine = served_engine
    await _run_requests(engine, n=2, max_tokens=4)
    hist = engine.core.metrics.prefill_step
    assert hist.n > 0
    mean_step_s = hist.total / hist.n

    rec = StepRecorder()
    clock = LoopClock(rec, "main")
    metrics = EngineMetrics()
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        clock.switch("admit")
        clock.switch("other")
        step = clock.begin("dispatch")
        cut = stepstats.PrefillCut(clock, step)
        for name in ("compute", "emit", "activate"):
            step.mark(name)
        clock.close_cut(cut, stepstats._now())
        step.mark("counters")
        clock.close(step, "prefill")
        phases = step.phases()
        slow = rec.observe("prefill", phases, active_slots=1, tokens=16,
                           request_ids={"0": "a"}, span=step,
                           extra={"chunk": {"index": 0, "pos": 0, "of": 16}})
        metrics.record_step_phases(phases, slow=slow)
        clock.resume(step)
    per_step = (time.perf_counter() - t0) / n
    record = rec.snapshot(limit=1)["records"][0]
    assert [name for name, _at, _dur in record["spans"]] == [
        "dispatch", "compute", "emit", "activate", "counters"]
    assert cut.parts["own"] > 0 and cut.parts["others"] == 0
    assert clock.step_seconds_by_kind["prefill"] == pytest.approx(
        clock.acc["step"])
    assert per_step < 0.01 * mean_step_s, (
        f"instrumentation {per_step * 1e6:.1f}µs/step vs mean prefill step "
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
    import os
    import threading

    from llmlb_tpu.engine.profiling import ProfileManager

    mgr = ProfileManager(trace_root=str(tmp_path))
    assert mgr.wait_idle(0.01) is True  # idle from construction
    mgr.start(30)
    assert mgr.wait_idle(0.01) is False  # recording: the wait parks

    woke = {}

    def waiter():
        assert mgr.wait_idle(10.0) is True
        woke["at"] = time.perf_counter()
        # idle means written: the capture's files are on disk and it is in
        # the ledger by the time a waiter wakes (the /debug/profile bug:
        # idle used to be set before stop_trace had written anything)
        woke["files"] = sum(len(f) for _r, _d, f in os.walk(str(tmp_path)))
        woke["captures"] = len(mgr.status()["captures"])

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    t_stop = time.perf_counter()
    mgr.stop()
    t_stopped = time.perf_counter()
    t.join(timeout=5)
    assert not t.is_alive()
    assert woke["files"] > 0 and woke["captures"] == 1
    # the waiter wakes with the stop itself — not before the trace is
    # written, and not a later poll tick after it
    assert woke["at"] >= t_stop
    assert woke["at"] - t_stopped < 0.045, (
        f"wait_idle woke {(woke['at'] - t_stopped) * 1000:.1f}ms after the "
        f"stop returned — still polling?"
    )
