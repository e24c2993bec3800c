"""The readers of a request's way in (PR 50): each against a hand-worked
`collected`, None on one shaped like a parent's (a program that serves none
of the fields), and a rehearsal on the CPU that reports every one of them.
Names and values only: no position in BENCHMARK.json, no parent's reading."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf
from benchmark import way_in

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal_way_in", "BENCHMARK.json")
RUN = os.path.join(mf.ROOT, "benchmark", "run.py")
CELL = "mistral-7b-l16.chat-paced"

NEW = {"service.accept_p50_s": "service",
       "sched.inbox_wait_p50_s": "scheduler",
       "sched.place_wait_p50_s": "scheduler",
       "sched.prefill_span_p50_s": "scheduler",
       "sched.first_fetch_wait_p50_s": "scheduler",
       "stream.first_frame_lag_mean_s": "service",
       "engine.ttft_unattributed_p50_s": "validity"}


def read(name, collected):
    return mf.load_module("layer_metrics", name).read(collected)


def _request(rid, ok=True, sample=True):
    return {"id": rid, "status": 200, "error": None, "completion_tokens": 8,
            "max_tokens": 8, "words": 8 if ok else 7, "first_s": 1.0,
            "send_s": 0.5, "due_s": 0.5, "last_s": 2.0, "in_sample": sample}


def _entry(rid, accept, inbox, place, prefill, first_fetch, chunks=1,
           prefill_seq=10):
    return {"id": rid, "accept": accept, "inbox": inbox, "place": place,
            "prefill": prefill, "first_fetch": first_fetch, "chunks": chunks,
            "cached_tokens": 0, "prefill_seq": prefill_seq}


def worked() -> dict:
    """Three good requests of the sample, one failed, one of the ramp; their
    first tokens came with two decode records (one of them also brought the
    ramp request's, which no reader counts); r3 was chunked."""
    requests = [_request("r1"), _request("r2"), _request("r3"),
                _request("r4", ok=False), _request("r0", sample=False)]
    steps = [
        {"seq": 11, "kind": "prefill", "spans": []},
        {"seq": 12, "kind": "decode", "dispatched_ahead": True, "first_tokens": [
            _entry("r1", 0.001, 0.040, 0.010, 0.020, 0.080),
            _entry("r0", 0.009, 0.900, 0.900, 0.900, 0.900)]},
        {"seq": 13, "kind": "decode", "dispatched_ahead": False},
        {"seq": 14, "kind": "decode", "dispatched_ahead": False,
         "first_tokens": [
             _entry("r2", 0.003, 0.060, 0.030, 0.024, 0.090),
             _entry("r3", 0.002, 0.020, 0.005, 0.300, 0.084, chunks=3),
             # the failed request's: its latencies are no part of the sample
             _entry("r4", 0.5, 0.5, 0.5, 0.5, 0.5)]},
    ]
    stream = {"frames_total": 100, "frame_seconds_total": 0.1,
              "first_frames_total": 10, "first_frame_seconds_total": 0.02}
    stream_end = {"frames_total": 900, "frame_seconds_total": 0.9,
                  "first_frames_total": 50, "first_frame_seconds_total": 0.1}
    return {
        "requests": requests,
        "sample": [r for r in requests if r["in_sample"]],
        "steps": steps,
        "timelines": {"r1": {"ttft_s": 0.1502, "queue_wait_s": 0.05},
                      "r2": {"ttft_s": 0.2040, "queue_wait_s": 0.09},
                      # no `finished` event yet: left out of the validity
                      "r3": {"ttft_s": None, "queue_wait_s": 0.025}},
        "health_start": {"metrics": {"stream": stream}},
        "health_end": {"metrics": {"stream": stream_end}},
        "seconds": 20,
    }


WORKED = {
    "service.accept_p50_s": 0.002,
    "sched.inbox_wait_p50_s": 0.040,
    "sched.place_wait_p50_s": 0.010,
    "sched.prefill_span_p50_s": 0.024,
    "sched.first_fetch_wait_p50_s": 0.084,
    # (0.1 - 0.02) s over 40 first frames
    "stream.first_frame_lag_mean_s": 0.002,
    # r1: |.1502 - .150| = .0002; r2: |.2040 - .204| = 0
    "engine.ttft_unattributed_p50_s": 0.0001,
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_against_a_hand_worked_window(name):
    assert read(name, worked()) == pytest.approx(WORKED[name], abs=1e-9)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_reports_nothing_for_a_parents_program(name):
    """The parent serves health, steps, timelines and a sample, none of them
    with this PR's fields: every reader says None and none raises."""
    c = worked()
    for r in c["steps"]:
        r.pop("first_tokens", None)
    for end in ("start", "end"):
        for key in ("first_frames_total", "first_frame_seconds_total"):
            del c[f"health_{end}"]["metrics"]["stream"][key]
    assert read(name, c) is None
    assert read(name, {"steps": [], "sample": []}) is None
    assert read(name, {"sample": []}) is None


def test_the_join_is_by_id_and_names_the_fetch():
    c = worked()
    by_id = way_in.first_tokens(c)
    assert by_id["r1"]["fetch_seq"] == 12
    assert by_id["r1"]["fetch_dispatched_ahead"] is True
    assert by_id["r3"]["fetch_seq"] == 14 and by_id["r3"]["chunks"] == 3
    assert [r["id"] for r, _e in way_in.joined(c)] == ["r1", "r2", "r3"]
    # a stage a request never passed (a restored one has no prefill) has no
    # value; the others keep theirs
    del c["steps"][1]["first_tokens"][0]["prefill"]
    assert way_in.stage_values(c, "prefill") == [0.024, 0.300]
    assert len(way_in.stage_values(c, "inbox")) == 3
    # ... and such a request is no part of the validity reading
    assert way_in.ttft_unattributed(c) == [pytest.approx(0.0)]


def test_the_script_prints_the_stages_by_prompt_class_and_the_join(tmp_path):
    """scripts/way_in.py on a run's file: the classes, and each first token
    joined to its prefill and fetch records by seq."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "way_in_script", os.path.join(mf.ROOT, "scripts", "way_in.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    c = worked()
    for r in c["requests"]:
        r["prompt_tokens"] = 700 if r["id"] == "r3" else 200
    # r1's and r2's first prefill: 2 ms of dispatch, 18 of compute
    c["steps"][0] = {"seq": 10, "kind": "prefill", "dispatched_ahead": True,
                     "spans": [["dispatch", 0.0, 0.002],
                               ["compute", 0.002, 0.018]], "wall_s": 0.021}
    for r in c["steps"][1:]:
        r["spans"] = [["dispatch", 0.0, 0.001], ["compute", 0.001, 0.084]]
    path = tmp_path / "last_run.json"
    path.write_text(json.dumps({"steps": c["steps"],
                                "requests": c["requests"]}))
    out = script.read(str(path), rows=True)
    assert (out["sampled"], out["all"]["n"], out["one_shot"]["n"],
            out["chunked"]["n"], out["cached"]["n"]) == (4, 3, 2, 1, 0)
    assert out["all"]["first_fetch"]["p50"] == pytest.approx(84.0)
    assert out["one_shot"]["prefill"]["p50"] == pytest.approx(22.0)
    assert out["chunked"]["prefill"]["p50"] == pytest.approx(300.0)
    assert out["one_shot"]["engine_ttft"]["mean"] == pytest.approx(177.0)
    assert out["all"]["client_ttft"]["p50"] == pytest.approx(500.0)
    join = out["join"]
    assert join["first_prefills_ahead_pct"] == 100.0
    assert join["fetches_ahead_pct"] == pytest.approx(33.3, abs=0.05)
    assert join["fetch_record_compute"]["p50"] == pytest.approx(84.0)
    # seq 10 -> 12: one decode record; seq 10 -> 14: three
    assert join["decode_records_from_prefill_to_fetch"]["p90"] > 1
    # inbox + place, and with the first prefill's 20 ms to its compute's end
    short = join["queue_wait_vs_stages"]["inbox_plus_place"]["p50"]
    whole = join["queue_wait_vs_stages"]["to_first_prefill_done"]["p50"]
    assert (short, whole) == (pytest.approx(50.0), pytest.approx(70.0))
    assert [row["id"] for row in out["rows"]] == ["r1", "r2", "r3"]
    assert out["rows"][0]["fetch_seq"] == 12
    # a parent's file: nothing joined, nothing raised
    for r in c["steps"]:
        r.pop("first_tokens", None)
    path.write_text(json.dumps({"steps": c["steps"],
                                "requests": c["requests"]}))
    assert script.read(str(path))["all"]["n"] == 0


def test_a_window_with_no_first_frame_divides_by_nothing():
    c = worked()
    c["health_end"] = c["health_start"]
    assert read("stream.first_frame_lag_mean_s", c) is None


def test_the_manifest_names_every_reader_for_the_cell_that_has_ttft():
    manifest = mf.load(mf.MANIFEST_PATH)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer in NEW.items():
        m = by_name[name]
        assert CELL in m["workloads"]  # a later PR may append cells
        assert (m["moves"], m["layer"], m["unit"], m["better"],
                m["source"]) == ("norm_latency_p50_s", layer, "s", "lower",
                                 "program_span")
    got = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW) <= got
    other = {m["name"] for m in mf.metrics_for(
        manifest, "per_layer", "mistral-7b-l16.decode-saturated")}
    assert not set(NEW) & other
    assert mf.check(manifest) == []


def test_a_rehearsal_reports_every_new_metric():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", MANIFEST, "--workload",
         "tiny.way-in", "--seed", "2147483689", "--seconds", "3", "--trace",
         "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    for name in NEW:
        assert got[name] >= 0, name
    assert got["engine.ttft_unattributed_p50_s"] < 0.001
    # the two halves of the wait the flight recorder reads as one
    assert (got["sched.inbox_wait_p50_s"] + got["sched.place_wait_p50_s"]
            < got["client.ttft_p50_s"])
    assert got["service.accept_p50_s"] > 0
    assert got["sched.prefill_span_p50_s"] > 0
    assert got["sched.first_fetch_wait_p50_s"] > 0
    assert got["stream.first_frame_lag_mean_s"] > 0
    # last_run.json keeps the whole records: the entries are in it
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny.way-in",
                           "last_run.json")) as f:
        last = json.load(f)
    entries = [e for r in last["steps"] for e in r.get("first_tokens", ())]
    assert entries and all(e["id"].startswith("bench-2147483689-")
                           for e in entries)
