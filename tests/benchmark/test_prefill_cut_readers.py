"""The readers of the cut of a request's `prefill` stage and of what a
prefill's counter reads cost (PR 66): each against a hand-worked
`collected`, None on one shaped like a parent's (a program that serves none
of the fields), the seven entries of BENCHMARK.json found by NAME (no
position in the file, no parent's reading), and a rehearsal on the CPU that
reports every one of them."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_prefill_cut", "BENCHMARK.json")
CELL = "dots3-note-prev-l5.long-doc-notes"

NEW = {"sched.prefill_own_mean_s": ("s", "program_counter", "scheduler"),
       "sched.prefill_others_mean_s": ("s", "program_counter", "scheduler"),
       "sched.prefill_decode_mean_s": ("s", "program_counter", "scheduler"),
       "sched.prefill_loop_mean_s": ("s", "program_counter", "scheduler"),
       "sched.prefill_chunks_mean": ("chunks", "program_counter",
                                     "scheduler"),
       "sched.prefill_cut_unattributed_p50_s": ("s", "program_span",
                                                "validity"),
       "sched.prefill_counters_read_share": ("%", "program_span",
                                             "scheduler")}


def read(name, collected):
    return mf.load_module("layer_metrics", name).read(collected)


def _request(rid, ok=True, sample=True):
    return {"id": rid, "status": 200, "error": None, "completion_tokens": 8,
            "max_tokens": 8, "words": 8 if ok else 7, "first_s": 1.0,
            "send_s": 0.5, "due_s": 0.5, "last_s": 2.0, "in_sample": sample}


def _entry(rid, prefill, cut=None, chunks=1):
    entry = {"id": rid, "inbox": 0.01, "place": 0.02, "prefill": prefill,
             "first_fetch": 0.05, "chunks": chunks, "cached_tokens": 0,
             "prefill_seq": 10}
    if cut is not None:
        entry["prefill_cut"] = dict(zip(("own", "others", "decode", "loop"),
                                        cut))
    return entry


def _way_in(requests, chunks, own, others, decode, loop):
    return {"metrics": {"way_in": {
        "requests_total": requests + 7, "seconds_total": {"prefill": 1.0},
        "prefill_cut_requests_total": requests,
        "prefill_cut_chunks_total": chunks,
        "prefill_cut_seconds_total": {"own": own, "others": others,
                                      "decode": decode, "loop": loop}}}}


def _prefill(seq, wall, spans):
    return {"seq": seq, "kind": "prefill", "wall_s": wall,
            "spans": [[name, 0.0, dur] for name, dur in spans]}


def worked() -> dict:
    """The window admitted 4 requests with a cut (10 -> 14) that took 80
    prefill dispatches (100 -> 180): 20 chunks a request. Their parts grew
    by 4.8, 36.0, 3.6 and 0.4 s: 1.2, 9.0, 0.9 and 0.1 s a request. Three
    sampled requests joined: r1 and r2 with cuts that miss their stage by
    2 us and 6 us, r3 a rider with no cut; r4 failed, r0 is the ramp's.
    Four prefill records: 50 + 50 + 60 + 40 = 200 ms of wall, three of them
    with a `counters` span (4 + 3 + 5 = 12 ms: 6%), one that left ahead
    without; a decode record's spans count for nothing."""
    requests = [_request("r1"), _request("r2"), _request("r3"),
                _request("r4", ok=False), _request("r0", sample=False)]
    steps = [
        _prefill(11, 0.050, [("dispatch", 0.006), ("compute", 0.036),
                             ("emit", 0.004), ("counters", 0.004)]),
        _prefill(12, 0.050, [("dispatch", 0.006), ("compute", 0.040),
                             ("emit", 0.001), ("counters", 0.003)]),
        {"seq": 13, "kind": "decode", "wall_s": 0.046,
         "spans": [["compute", 0.0, 0.040], ["counters", 0.0, 0.5]],
         "first_tokens": [
             _entry("r1", 11.2, (1.2, 9.0, 0.9, 0.100002), chunks=20),
             _entry("r0", 99.0, (1.0, 1.0, 1.0, 1.0))]},
        _prefill(14, 0.060, [("dispatch", 0.006), ("compute", 0.040),
                             ("emit", 0.001), ("activate", 0.008),
                             ("counters", 0.005)]),
        _prefill(15, 0.040, [("dispatch", 0.006),
                             ("activate_inflight", 0.034)]),
        {"seq": 16, "kind": "decode", "wall_s": 0.046, "first_tokens": [
            _entry("r2", 8.0, (1.0, 6.0, 0.9, 0.099994), chunks=16),
            _entry("r3", 0.05), _entry("r4", 5.0, (1.0, 1.0, 1.0, 1.0))]},
    ]
    return {"sample": [r for r in requests if r["in_sample"]],
            "requests": requests, "steps": steps,
            "health_start": _way_in(10, 100, 10.0, 20.0, 5.0, 1.0),
            "health_end": _way_in(14, 180, 14.8, 56.0, 8.6, 1.4)}


def parent() -> dict:
    """A program before PR 66: the way in is served, the cut is not, and no
    record has a `counters` span."""
    collected = worked()
    for end in ("health_start", "health_end"):
        way_in = collected[end]["metrics"]["way_in"]
        for key in [k for k in way_in if k.startswith("prefill_cut_")]:
            del way_in[key]
    for record in collected["steps"]:
        record["spans"] = [s for s in record.get("spans", ())
                           if s[0] != "counters"]
        for entry in record.get("first_tokens", ()):
            entry.pop("prefill_cut", None)
    return collected


@pytest.mark.parametrize("name, want", [
    ("sched.prefill_own_mean_s", 1.2),
    ("sched.prefill_others_mean_s", 9.0),
    ("sched.prefill_decode_mean_s", 0.9),
    ("sched.prefill_loop_mean_s", 0.1),
    ("sched.prefill_chunks_mean", 20.0),
    ("sched.prefill_cut_unattributed_p50_s", 4e-6),
    ("sched.prefill_counters_read_share", 6.0),
])
def test_a_reader_on_a_hand_worked_run(name, want):
    assert read(name, worked()) == pytest.approx(want, rel=1e-6, abs=1e-9)


def test_the_four_means_sum_to_the_mean_stage():
    collected = worked()
    parts = [read(f"sched.prefill_{part}_mean_s", collected)
             for part in ("own", "others", "decode", "loop")]
    assert sum(parts) == pytest.approx((4.8 + 36.0 + 3.6 + 0.4) / 4)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_finds_nothing_on_a_parents_run(name):
    assert read(name, parent()) is None
    assert read(name, {"steps": [], "sample": []}) is None


@pytest.mark.parametrize("name", [n for n in sorted(NEW)
                                  if "mean" in n])
def test_a_window_that_admitted_nobody_has_no_mean(name):
    collected = worked()
    collected["health_end"] = collected["health_start"]
    assert read(name, collected) is None


def test_only_the_prefill_records_own_counters_spans_are_the_share():
    collected = worked()
    # a family without counters: no prefill record has the span
    for record in collected["steps"]:
        if record["kind"] == "prefill":
            record["spans"] = [s for s in record["spans"]
                               if s[0] != "counters"]
    assert read("sched.prefill_counters_read_share", collected) is None


def test_the_script_prints_the_chunked_class_cut(tmp_path):
    """scripts/way_in.py on a run's file: mean and median a part, and the
    share of the stage each part is, over the class's entries with a cut;
    null on a parent's file."""
    spec = importlib.util.spec_from_file_location(
        "way_in_script", os.path.join(mf.ROOT, "scripts", "way_in.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    path = tmp_path / "last_run.json"

    def printed(collected):
        for r in collected["requests"]:
            r["prompt_tokens"] = 9000
        path.write_text(json.dumps({"steps": collected["steps"],
                                    "requests": collected["requests"]}))
        return script.read(str(path), rows=True)

    out = printed(worked())
    assert (out["chunked"]["n"], out["one_shot"]["n"]) == (2, 1)
    cut = out["chunked"]["prefill_cut"]
    assert cut["n"] == 2
    # r1 and r2: own 1.2 and 1.0 s of stages of 11.2 and 8.0 s
    assert cut["own"] == {"mean": 1100.0, "p50": 1100.0,
                          "share_pct": pytest.approx(11.5, abs=0.05)}
    assert cut["others"]["mean"] == 7500.0
    assert cut["others"]["share_pct"] == pytest.approx(78.1, abs=0.05)
    assert sum(cut[part]["share_pct"] for part in script.CUT
               ) == pytest.approx(100.0, abs=0.2)
    assert out["one_shot"]["prefill_cut"] is None  # r3 rode a burst
    assert out["rows"][0]["prefill_cut"]["others"] == 9000.0
    assert "prefill_cut" not in out["rows"][2]
    before = printed(parent())
    assert before["chunked"]["n"] == 2
    assert before["chunked"]["prefill_cut"] is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_names_the_reader_for_the_one_cell(name):
    manifest = mf.load(mf.MANIFEST_PATH)
    entries = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    unit, source, layer = NEW[name]
    assert entries[0] == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "tpot_p50_s", "workloads": [CELL]}
    # the cell reports the end-to-end metric the reader moves
    assert "tpot_p50_s" in [m["name"] for m in mf.metrics_for(
        manifest, "end_to_end", CELL)]
    reported = {c["name"] for c in manifest["workloads"]
                if name in [m["name"] for m in mf.metrics_for(
                    manifest, "per_layer", c["name"])]}
    assert reported == {CELL}


def test_a_rehearsal_reports_all_seven():
    """`run.py --rehearse --trace 1` through the real launcher, gateway and
    generator at a CI size of the cell's family: four callers in a closed
    loop, prompts of 40-90 tokens in chunks of 32 (2-3 a prompt, in
    rotation beside decoding rows). Not a measurement: the values are a
    CPU's; what is held is that every reader finds its field, that the cut
    telescopes and that the records say which chunk they are."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-sparse.long-closed",
         "--seed", "2147483655", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = {name: m["value"] for name, m in line["metrics"].items()}
    assert set(NEW) <= set(metrics)
    assert metrics["sched.prefill_cut_unattributed_p50_s"] < 0.001
    assert 2 <= metrics["sched.prefill_chunks_mean"] <= 3
    assert metrics["sched.prefill_own_mean_s"] > 0
    assert metrics["sched.prefill_others_mean_s"] > 0
    assert metrics["sched.prefill_decode_mean_s"] > 0
    assert 0 < metrics["sched.prefill_counters_read_share"] < 50
    assert metrics["sched.loop_unattributed_share"] < 2
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-sparse.long-closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    entries = [e for r in steps for e in r.get("first_tokens", ())]
    assert entries and all("prefill_cut" in e for e in entries)
    for e in entries:
        assert sum(e["prefill_cut"].values()) == pytest.approx(
            e["prefill"], abs=1e-5)
    chunks = [r for r in steps if r["kind"] == "prefill"]
    assert chunks and all(
        r["chunk"]["pos"] == 32 * r["chunk"]["index"] and
        r["chunk"]["pos"] + r["tokens"] <= r["chunk"]["of"] for r in chunks)
    assert all([name for name, _at, _dur in r["spans"]][-1] == "counters"
               for r in chunks)
