"""The readers of a token's way out (PR 36): each against a hand-worked
`collected`, None on one shaped like a parent's (a program that serves no
such field), and a rehearsal on the CPU that reports every one of them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf
from benchmark import stream_window

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal_stream", "BENCHMARK.json")
RUN = os.path.join(mf.ROOT, "benchmark", "run.py")

NEW = ("stream.event_wait_mean_s", "stream.frame_cost_mean_s",
       "stream.write_wait_share", "stream.tokens_per_frame",
       "stream.delivery_lag_mean_s", "gateway.stream_lag_mean_s",
       "host.process_cpu_share", "host.http_loop_cpu_share",
       "host.event_bridge_cpu_share", "host.step_loop_cpu_share",
       "sched.host_offcpu_share", "host.gc_share")


def read(name, collected):
    return mf.load_module("layer_metrics", name).read(collected)


def _health(uptime, stream, cpu, gc_s):
    return {"engine": {"uptime_s": uptime},
            "metrics": {"stream": stream, "cpu_seconds_total": cpu,
                        "gc": {"collections_total": {"0": 3, "1": 0, "2": 0},
                               "seconds_total": gc_s}}}


def _request(send, last, ok=True):
    return {"status": 200, "error": None, "completion_tokens": 8,
            "max_tokens": 8, "words": 8 if ok else 7, "first_s": send + 0.1,
            "send_s": send, "last_s": last, "in_sample": True}


def worked() -> dict:
    """A window of 20 s: 4,000 events of one token each and 40 dones took
    8.08 s in the queues, 4,000 frames took 0.6 s of which 0.06 in paused
    writes, 40 streams finished, made in 400 s and delivered in 480 s."""
    start = _health(
        100.0,
        {"events_total": 1000, "tokens_total": 990,
         "event_wait_seconds_total": 1.0, "frames_total": 990,
         "frame_seconds_total": 0.2, "write_wait_seconds_total": 0.0,
         "streams_finished_total": 10, "stream_seconds_total": 100.0,
         "made_seconds_total": 90.0, "event_backlog_max": 3,
         "events_queued": 2},
        {"process": 50.0, "step_loop": 20.0, "http_loop": 10.0,
         "event_bridge": 4.0, "prewarm": 1.0, "other": 15.0}, 0.5)
    end = _health(
        120.0,
        {"events_total": 5040, "tokens_total": 4990,
         "event_wait_seconds_total": 9.08, "frames_total": 4990,
         "frame_seconds_total": 0.8, "write_wait_seconds_total": 0.06,
         "streams_finished_total": 50, "stream_seconds_total": 580.0,
         "made_seconds_total": 490.0, "event_backlog_max": 9,
         "events_queued": 0},
        {"process": 80.0, "step_loop": 32.0, "http_loop": 28.0,
         "event_bridge": 8.0, "prewarm": 1.0, "other": 11.0}, 0.7)
    steps = [
        # 10 ms of host spans (compute left out), 4 ms of them on a CPU;
        # the gap before it: 2 ms, 1 ms on a CPU
        {"spans": [["host_sync", 0.0, 0.002], ["compute", 0.002, 0.02],
                   ["emit", 0.022, 0.008]], "host_cpu_s": 0.004,
         "gap_cpu_s": 0.001,
         "since_prev": {"admit_s": 0.001, "control_s": 0.0,
                        "record_s": 0.001, "idle_s": 0.0, "other_s": 0.0}},
        # a gap with an idle sleep in it is left out; the step counts
        {"spans": [["emit", 0.0, 0.004]], "host_cpu_s": 0.004,
         "gap_cpu_s": 0.0005,
         "since_prev": {"admit_s": 0.0, "control_s": 0.0, "record_s": 0.001,
                        "idle_s": 0.03, "other_s": 0.0}},
        {"phases_s": {}},  # a record with no measured spans
    ]
    sample = [_request(0.0, 13.0), _request(1.0, 15.0),
              _request(2.0, 3.0, ok=False)]
    return {"health_start": start, "health_end": end, "steps": steps,
            "sample": sample, "seconds": 20}


WORKED = {
    "stream.event_wait_mean_s": 8.08 / 4040,
    "stream.frame_cost_mean_s": 0.6 / 4000,
    "stream.write_wait_share": 10.0,
    "stream.tokens_per_frame": 1.0,
    "stream.delivery_lag_mean_s": (480.0 - 400.0) / 40,
    # the client's two good streams took 13 and 14 s, the engine's 12 s
    "gateway.stream_lag_mean_s": 13.5 - 12.0,
    "host.process_cpu_share": 150.0,
    "host.http_loop_cpu_share": 90.0,
    "host.event_bridge_cpu_share": 20.0,
    "host.step_loop_cpu_share": 60.0,
    # wall 10 + 2 + 4 ms, CPU 4 + 1 + 4 ms
    "sched.host_offcpu_share": 100.0 * (16 - 9) / 16,
    "host.gc_share": 1.0,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_against_a_hand_worked_window(name):
    assert read(name, worked()) == pytest.approx(WORKED[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_reports_nothing_for_a_parents_program(name):
    """The parent serves health, steps and a sample, none of them with this
    PR's fields: every reader says None and none raises."""
    c = worked()
    for end in ("start", "end"):
        for block in ("stream", "cpu_seconds_total", "gc"):
            del c[f"health_{end}"]["metrics"][block]
    for r in c["steps"]:
        r.pop("host_cpu_s", None)
        r.pop("gap_cpu_s", None)
    assert read(name, c) is None
    assert read(name, {"steps": [], "sample": []}) is None


def test_an_empty_window_divides_by_nothing():
    c = worked()
    c["health_end"] = c["health_start"]
    for name in NEW:
        if name != "sched.host_offcpu_share":
            assert read(name, c) is None, name
    assert stream_window.window_seconds(c) is None
    assert stream_window.ratio(worked(), "tokens_total", "no_such") is None


def test_the_manifest_names_every_new_reader_for_the_cells_that_can():
    """Every cell but `mistral-7b-l16.chat-paced`: test_readers.py holds
    that cell's traced line to the whole of its list on a hand-made run
    that serves none of this PR's fields, and a PR that is no `benchmark`
    PR may not edit it (PERF.md section 7 hands the fixture, and the
    `workloads` keys with it, to the next one)."""
    manifest = mf.load(mf.MANIFEST_PATH)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # appended, in this order
    assert tuple(m["name"] for m in manifest["per_layer"][-len(NEW):]) == NEW
    cells = [c["name"] for c in manifest["workloads"]
             if c["name"] != "mistral-7b-l16.chat-paced"]
    assert len(cells) == 3
    for name in NEW:
        m = by_name[name]
        assert sorted(m["workloads"]) == sorted(cells)
        assert m["moves"] == "tpot_p50_s"
        assert m["layer"] == ("gateway" if name.startswith("gateway.") else
                              "service" if name.startswith("stream.") else
                              "scheduler")
    for cell in cells:
        got = {m["name"] for m in mf.metrics_for(manifest, "per_layer", cell)}
        assert set(NEW) <= got


def test_a_rehearsal_reports_every_new_metric():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", MANIFEST, "--workload",
         "tiny.closed", "--seed", "2147483661", "--seconds", "2", "--trace",
         "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    # one token a frame, but for the frames in flight at the window's ends
    assert got["stream.tokens_per_frame"] == pytest.approx(1.0, abs=0.05)
    assert got["stream.write_wait_share"] == 0.0
    assert got["stream.event_wait_mean_s"] > 0
    assert got["stream.frame_cost_mean_s"] > 0
    assert got["stream.delivery_lag_mean_s"] >= 0
    classes = sum(got[f"host.{c}_cpu_share"]
                  for c in ("http_loop", "event_bridge", "step_loop"))
    assert 0 < classes <= got["host.process_cpu_share"] * 1.001
    assert 0 <= got["sched.host_offcpu_share"] <= 100
    assert 0 <= got["host.gc_share"] < 100
