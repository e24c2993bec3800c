"""Every end-to-end function and per-layer reader on one hand-made run, and
the final line the harness builds from them."""

import json

import pytest

from benchmark import manifest as mf
from benchmark import peaks, samples

MANIFEST = mf.load()
CONFIG = mf.load_config(MANIFEST, "mistral-7b-l16")
with open(mf.HERE + "/settings.json") as f:
    SETTINGS = json.load(f)


def req(i, due, send, first, last, words, *, prompt=100, sample=True, ok=True):
    return {"id": f"r{i}", "due_s": due, "send_s": send, "first_s": first,
            "last_s": last, "end_s": last, "prompt_tokens": prompt,
            "max_tokens": words if ok else words + 1, "words": words,
            "completion_tokens": words, "status": 200, "error": None,
            "in_sample": sample, "kind": "request", "text": ""}


LOOP_START = {"main": {"step": 10.0, "admit": 1.0, "control": 0.5,
                       "record": 0.2, "idle": 30.0, "other": 0.3}}
LOOP_END = {"main": {"step": 50.0, "admit": 2.0, "control": 0.5,
                     "record": 0.7, "idle": 40.0, "other": 0.8}}
DECODE_SPANS = [("host_sync", 0.01), ("dispatch", 0.01), ("compute", 0.20),
                ("fetch", 0.02), ("emit", 0.01)]


def ledger(programs: int) -> dict:
    """An engine's `compile` block (llmlb_tpu/engine/compilelog.py)."""
    def block(n, trace, lower, backend):
        return {"programs_total": n, "cache_hits_total": n,
                "repeat_builds_total": 0,
                "seconds_total": {"trace": trace, "lower": lower,
                                  "backend": backend}}
    return {**block(programs, 20.0, 15.0, 12.5),
            "by_thread": {"loop": block(programs - 8, 10.0, 7.0, 4.5),
                          "prewarm": block(4, 10.0, 8.0, 8.0),
                          "other": block(4, 0.0, 0.0, 0.0)}}


def step(record: dict, t0: float, spans: list, since_prev: dict,
         active_slots: int) -> dict:
    """A step record as the engine serves it since PR 24: `record` plus its
    stamps, its spans laid end to end from `t0`, and the account of the
    time since the previous record."""
    at, out = 0.0, []
    for name, dur in spans:
        out.append([name, at, dur])
        at += dur
    gaps = dict.fromkeys(("admit_s", "control_s", "record_s", "idle_s",
                          "other_s"), 0.0)
    return {**record, "t0_s": t0, "t1_s": t0 + at, "wall_s": at, "spans": out,
            "since_prev": {**gaps, **since_prev},
            "active_slots": active_slots,
            "builds": {"count": 0, "names": []}}


def collected():
    requests = [
        req(1, 0.0, 0.001, 0.301, 1.301, 11),   # ttft .301  tpot .1
        req(2, 1.0, 1.002, 1.502, 3.502, 21),   # ttft .502  tpot .1
        req(3, 2.0, 2.003, 2.203, 5.203, 11),   # ttft .203  tpot .3
        req(4, 3.0, 3.000, 3.4, 4.4, 11, ok=False),  # short: failed
        req(5, -1.0, -1.0, -0.5, 2.0, 26, sample=False),  # the ramp's
    ]
    return {
        "seconds": 10, "settings": SETTINGS, "config": CONFIG,
        "requests": requests, "sample": [r for r in requests if r["in_sample"]],
        "window_tokens": 70, "setup_s": 99.5,
        "timelines": {"r1": {"ttft_s": 0.290, "queue_wait_s": 0.010},
                      "r2": {"ttft_s": 0.480, "queue_wait_s": 0.030},
                      "r3": {"ttft_s": 0.190, "queue_wait_s": 0.020}},
        "steps": [
            step({"ts": 1001.0, "kind": "decode", "total_s": 0.25, "tokens": 8,
                  "phases_s": {"plan": 0.01, "dispatch": 0.01, "compute": 0.20,
                               "fetch": 0.02, "emit": 0.01}},
                 500.0, DECODE_SPANS, {"admit_s": 0.01}, 4),
            step({"ts": 1002.0, "kind": "prefill", "total_s": 0.15, "tokens": 300,
                  "phases_s": {"plan": 0.02, "dispatch": 0.01, "compute": 0.10,
                               "emit": 0.02}},
                 501.0, [("dispatch", 0.01), ("compute", 0.10),
                         ("activate", 0.02)], {"admit_s": 0.02}, 1),
            step({"ts": 1009.5, "kind": "prefill", "total_s": 0.10, "tokens": 200,
                  "phases_s": {"compute": 0.10}},
                 508.5, [("compute", 0.10)], {}, 1),
            # a second decode step, so that a stretch between two exists; a
            # copy of the first, which leaves sched.host_share where it was
            step({"ts": 1009.9, "kind": "decode", "total_s": 0.25, "tokens": 8,
                  "phases_s": {"plan": 0.01, "dispatch": 0.01, "compute": 0.20,
                               "fetch": 0.02, "emit": 0.01}},
                 509.0, DECODE_SPANS, {"admit_s": 0.01}, 4),
        ],
        "health_start": {"metrics": {"prefix_cached_tokens_total": 1000,
                                     "loop_seconds_total": LOOP_START,
                                     "compile": ledger(240)}},
        "health_end": {"metrics": {"prefix_cached_tokens_total": 1100,
                                   "loop_seconds_total": LOOP_END,
                                   "compile": ledger(240)}},
        "trace": {"busy_s": 6.0, "window_s": 8.0, "wall_start": 1005.0,
                  "wall_stop": 1009.9,
                  "modules": {"jit_many": {"count": 30, "time_s": 6.0, "median_s": 0.24},
                              "jit_prefill_into_pages": {"count": 2, "time_s": 0.08, "median_s": 0.04},
                              "jit_prefill_extend_pages": {"count": 1, "time_s": 0.02, "median_s": 0.02}},
                  "ops": {"paged_flash_decode_bf16_32_8_4_128_": {"time_s": 0.5, "count": 3840},
                          "paged_flash_decode_quant": {"time_s": 9.0, "count": 1},
                          "fusion_bf16_32_14336_": {"time_s": 1.0, "count": 99}},
                  "breakdown": {"device_ops": [["x", 1.0]], "idle_gaps": [["decode.emit", 0.01]]}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 11_000_000_000},
        "peaks": peaks.peaks_for("TPU v5 lite"),
        "engine": {"decode_burst": 8, "param_bytes": 7_500_000_000,
                   "n_params": 3_750_000_000},
        "compiles_in_window": 0, "correctness": {"ok": True},
    }


def e2e(name):
    return mf.load_module("e2e_metrics", name).read(collected())


def layer(name, c=None):
    return mf.load_module("layer_metrics", name).read(c or collected())


def test_end_to_end_metrics_by_hand():
    # the failed request misses every latency: three samples
    assert layer("client.ttft_p50_s") == pytest.approx(0.301)
    # (last - due) / tokens: 1.301/11, 2.502/21, 3.203/11
    assert e2e("norm_latency_p50_s") == pytest.approx(2.502 / 21)
    assert layer("client.ttft_p90_s") == pytest.approx(0.301 + 0.8 * (0.502 - 0.301))
    assert e2e("tpot_p50_s") == pytest.approx(0.1)
    assert e2e("out_tok_per_s") == pytest.approx(7.0)


@pytest.mark.parametrize("name,want", [
    # client first - send, less the engine's ttft: .010, .020, .010
    ("gateway.ttft_overhead_p50_s", 0.010),
    ("sched.queue_wait_p50_s", 0.020),
    ("client.ttft_p50_s", 0.301),
    # host phases .05 + .05 + 0 over .50 of steps
    ("sched.host_share", 20.0),
    # 100 cached of the 400 prompt tokens sent inside the window
    ("cache.prefix_hit_share", 25.0),
    ("model.decode_step_s", 0.03),
    # the prefill record inside the traced wall interval: 200 tokens / 0.1 s
    ("model.prefill_tok_per_s", 2000.0),
    ("device.idle_share", 25.0),
    ("device.hbm_peak_bytes", 11_000_000_000),
    ("client.send_lag_p99_s", 0.003 - 0.00003),
    ("engine.compiles_in_window", 0.0),
])
def test_layer_readers_by_hand(name, want):
    assert layer(name) == pytest.approx(want, rel=1e-6, abs=1e-12)


def test_live_kv_tokens_by_hand():
    c = collected()
    # at t in [2.5, 3.3]: r2 (until 3.502) and r3 decode; r5 ended at 2.0
    tokens, rows = samples.live_kv_tokens(c, 2.5, 3.3, points=100)
    assert rows == pytest.approx(2.0)
    r2 = 100 + 21 * (2.9 - 1.502) / 2.0   # at the midpoint
    r3 = 100 + 11 * (2.9 - 2.203) / 3.0
    assert tokens == pytest.approx(r2 + r3, rel=1e-3)


def test_rooflines_are_shares_of_the_published_peaks():
    c = collected()
    live, rows = samples.live_kv_tokens(c, *samples.traced_interval(c))
    pk = peaks.peaks_for("TPU v5 lite")
    # decode program: weights less the embedding table, plus live KV
    w = mf.load_module("roofline", "decode_program").work(
        CONFIG, c["engine"], live_tokens=live, rows=rows)
    embed = 32000 * 4096 * 2
    kv = live * 16 * 8 * 128 * 2 * 2
    assert w["bytes"] == pytest.approx(7_500_000_000 - embed + kv)
    want = 100 * (w["bytes"] / pk["hbm_bytes_per_s"]) / 0.03
    assert layer("model.decode_program_roofline") == pytest.approx(want)
    assert 0 < want < 100
    # kernel: only the bf16 kernel's rows count, not the quant kernel's
    k = mf.load_module("roofline", "paged_flash_decode").work(
        CONFIG, c["engine"], live_tokens=live, rows=rows)
    assert k["bytes"] == pytest.approx(live * 8 * 128 * 2 * 2 + rows * 32 * 128 * 2 * 2)
    want = 100 * (3840 * k["bytes"] / pk["hbm_bytes_per_s"]) / 0.5
    assert layer("kernel.paged_flash_decode_roofline") == pytest.approx(want)


@pytest.mark.parametrize("keys", [
    {"num_local_experts": 8},                                   # Mixtral
    {"num_experts": 8},                                         # OLMoE
    {"num_experts": 8, "moe_intermediate_size": 14336,
     "intermediate_size": 999},                                 # Qwen-MoE
], ids=lambda k: "+".join(k))
def test_the_decode_roofline_counts_a_mixtures_routed_experts(keys):
    """A token multiplies by 2 of 8 experts whichever key names the 8 and
    whichever the width; a config with neither key is dense."""
    hf = {**CONFIG, "num_hidden_layers": 4, "num_experts_per_tok": 2,
          "intermediate_size": 14336, **keys}
    expert_params = 4 * 8 * 3 * 4096 * 14336
    n_params = expert_params + 500_000_000
    engine = {"param_bytes": 2 * n_params, "n_params": n_params}
    work = mf.load_module("roofline", "decode_program").work
    sparse = work(hf, engine, live_tokens=0, rows=1)
    routed = n_params - 32000 * 4096 - expert_params * 3 / 4
    assert sparse["flops"] == pytest.approx(2 * routed)
    assert sparse["bytes"] == 2 * n_params - 32000 * 4096 * 2  # every expert
    dense = work({k: v for k, v in hf.items() if k not in keys}, engine,
                 live_tokens=0, rows=1)
    assert dense["flops"] == pytest.approx(2 * (n_params - 32000 * 4096))


def test_a_reader_that_finds_nothing_returns_nothing():
    c = collected()
    c["trace"] = None
    c["timelines"] = {}
    for name in ("model.decode_step_s", "model.prefill_tok_per_s",
                 "model.decode_program_roofline", "device.idle_share",
                 "kernel.paged_flash_decode_roofline",
                 "gateway.ttft_overhead_p50_s", "sched.queue_wait_p50_s"):
        assert layer(name, c) is None, name


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    share, bound = peaks.roofline_share_pct(197e12, 0, 2.0, peaks.peaks_for("TPU v5 lite"))
    assert (share, bound) == (pytest.approx(50.0), "compute")


@pytest.mark.parametrize("trace", [False, True])
def test_the_final_line_has_the_contract_keys(trace):
    from benchmark.run import result_line

    cell = mf.cell(MANIFEST, "mistral-7b-l16.decode-saturated" if not trace
                   else "mistral-7b-l16.chat-paced")
    line = result_line(MANIFEST, cell, collected(), trace)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert (line["attempted"], line["failed"], line["correct"]) == (4, 1, False)
    section = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {m["name"] for m in mf.metrics_for(
        MANIFEST, section, cell["name"])}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    if trace:
        assert line["device"]["busy_s"] == 6.0 and line["device"]["window_s"] == 8.0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert line["metrics"]["setup_s"]["value"] == 99.5
        assert "breakdown" not in line
