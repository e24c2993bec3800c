"""The reader of the order a decode cycle took (PR 60), worked out by hand on
a built window: the share of the decode bursts that were queued behind their
predecessor before its fetch (`scheduler._decode_bursts`,
docs/scheduling.md "The four orders of a decode cycle"). A program whose
records carry no such field queues nothing, and that is what it reads: 0.0,
not nothing."""

import pytest

from benchmark import manifest as mf
from benchmark import run as bench_run

NAME = "sched.queued_behind_share"


def read(collected):
    return mf.load_module("layer_metrics", NAME).read(collected)


def burst(order: str) -> dict:
    """A decode record as the engine writes it: one of the three fields
    says which order the cycle took."""
    return {"kind": "decode",
            "queued_behind": order == "queued",
            "dispatched_ahead": order == "ahead",
            "ahead_blocked_by": None if order in ("queued", "ahead")
            else order}


STEPS = ([burst("first"), burst("queued"), burst("queued"),
          {"kind": "prefill", "dispatched_ahead": True},
          burst("ahead"), burst("queued"), burst("admission"),
          {"kind": "prefill", "dispatched_ahead": False},
          {"kind": "verify"}, burst("queued")])


def test_the_share_of_bursts_queued_behind_by_hand():
    # four of the seven decode records; the prefills and the verify step
    # are no bursts
    assert read({"steps": STEPS}) == pytest.approx(100.0 * 4 / 7)
    assert read({"steps": [burst("queued")] * 3}) == 100.0
    assert read({"steps": [burst("ahead"), burst("first")]}) == 0.0


def test_records_without_the_field_read_zero_not_nothing():
    """The parent's records: `dispatched_ahead` and `ahead_blocked_by`, no
    `queued_behind` — and the records of a commit before PR 39, with
    neither."""
    parents = [{k: v for k, v in r.items() if k != "queued_behind"}
               for r in STEPS]
    assert read({"steps": parents}) == 0.0
    assert read({"steps": [{"kind": "decode", "active_slots": 4}] * 5}) == 0.0


def test_a_window_without_a_decode_record_reads_nothing():
    assert read({"steps": []}) is None
    assert read({}) is None
    assert read({"steps": [{"kind": "prefill"}]}) is None


def test_the_manifest_names_the_reader_for_every_cell():
    """No `workloads` key: every cell reports `tpot_p50_s`, the metric it
    moves, and a cell added later reads it at once; its source is
    `sched.host_share`'s, the step records."""
    manifest = mf.load()
    assert mf.check(manifest) == []
    entry = {m["name"]: m for m in manifest["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "scheduler",
                     "moves": "tpot_p50_s"}
    assert entry["source"] == {m["name"]: m for m in manifest["per_layer"]}[
        "sched.host_share"]["source"]
    assert manifest["per_layer"][-1] is entry  # put at the end of its list
    for cell in manifest["workloads"]:
        assert NAME in {m["name"] for m in mf.metrics_for(
            manifest, "per_layer", cell["name"])}, cell["name"]


@pytest.mark.parametrize("queues", [True, False],
                         ids=["the-change", "the-parent"])
def test_a_traced_line_carries_the_share(queues):
    """`run.result_line` on a traced window: 0.0 is a value and is
    reported, on the parent's records too."""
    steps = STEPS if queues else [
        {k: v for k, v in r.items() if k != "queued_behind"} for r in STEPS]
    manifest = {"per_layer": [{"name": NAME, "unit": "%"}], "end_to_end": []}
    cell = {"name": "any.cell"}
    collected = {"steps": steps, "sample": [],
                 "correctness": {"ok": True}, "device": {}, "setup_s": 1.0}
    line = bench_run.result_line(manifest, cell, collected, trace=True)
    assert line["metrics"][NAME] == {
        "value": pytest.approx(100.0 * 4 / 7 if queues else 0.0),
        "unit": "%"}
