"""The reader of the share of a window's admissions whose prompt rode a
burst's first step (PR 61; `scheduler._admit_riding`, docs/scheduling.md "An
arrival rides a burst"), worked out by hand on a built window, beside its
sibling's cases in `test_order_readers.py` (whose records and helpers these
use). A program whose records carry no `admitted` lets nothing ride, and that
is what it reads: 0.0, not nothing — also where the window admitted nobody."""

import pytest

from benchmark import manifest as mf
from benchmark import run as bench_run
from tests.benchmark.test_order_readers import NAME, STEPS, burst

RIDES = "sched.mixed_admit_share"


def read(collected):
    return mf.load_module("layer_metrics", RIDES).read(collected)


def first_token(rid: str, chunks: int = 1) -> dict:
    """An entry of a record's `first_tokens`: the request whose first token
    the record's fetch brought."""
    return {"id": rid, "chunks": chunks, "prefill_seq": 1}


def rode(slot: int, tokens: int, rid: str) -> dict:
    """A decode record that admitted an arrival: its first token is in the
    burst's own fetch."""
    return {**burst("ahead"), "admitted": {"slot": slot,
                                           "prompt_tokens": tokens},
            "first_tokens": [first_token(rid)]}


ADMISSIONS = [
    {"kind": "prefill", "dispatched_ahead": False, "active_slots": 2},
    {**burst("first"), "first_tokens": [first_token("a"), first_token("b")]},
    rode(2, 90, "c"), burst("ahead"),
    # a chunked prompt: three prefill records, one admission
    {"kind": "prefill"}, burst("prefilling"), {"kind": "prefill"},
    burst("prefilling"), {"kind": "prefill"},
    {**burst("prefilling"), "first_tokens": [first_token("d", chunks=3)]},
    rode(0, 128, "e"),
    # a riding request cancelled before its first token: it rode all the same
    {**burst("ahead"), "admitted": {"slot": 1, "prompt_tokens": 64}},
]


def test_the_share_of_admissions_that_rode_by_hand():
    # c, e and the cancelled one rode; a, b and d were prefilled
    assert read({"steps": ADMISSIONS}) == pytest.approx(100.0 * 3 / 6)
    assert read({"steps": [rode(0, 70, "x"), rode(1, 99, "y")]}) == 100.0
    assert read({"steps": ADMISSIONS[:2] + ADMISSIONS[3:10]}) == 0.0


def test_records_without_admitted_read_zero_and_so_does_a_window_that_admits_nobody():
    """The parent's records carry `first_tokens` (since PR 50) and no
    `admitted`; a tree before PR 50 carries neither; a window of decode
    records in which nobody was admitted let nothing ride either: 0.0 each,
    a value every cell reports."""
    parents = [{k: v for k, v in r.items() if k != "admitted"}
               for r in ADMISSIONS]
    assert read({"steps": parents}) == 0.0
    assert read({"steps": STEPS}) == 0.0
    assert read({"steps": [{"kind": "decode", "active_slots": 4}] * 5}) == 0.0


def test_a_window_without_a_decode_record_reads_no_share_of_admissions():
    assert read({"steps": []}) is None
    assert read({}) is None
    assert read({"steps": [{"kind": "prefill"}]}) is None


def test_the_manifest_names_the_reader_for_every_cell():
    """No `workloads` key: every cell reports `tpot_p50_s`, the metric it
    moves, and a cell added later reads it at once; its source is
    `sched.host_share`'s, the step records. Put behind its sibling, at the
    end of the list as it stood: where a later entry goes is not this
    test's to say."""
    manifest = mf.load()
    assert mf.check(manifest) == []
    names = [m["name"] for m in manifest["per_layer"]]
    entry = manifest["per_layer"][names.index(RIDES)]
    assert entry == {"name": RIDES, "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "scheduler",
                     "moves": "tpot_p50_s"}
    assert entry["source"] == manifest["per_layer"][
        names.index("sched.host_share")]["source"]
    assert names.index(RIDES) == names.index(NAME) + 1
    for cell in manifest["workloads"]:
        assert RIDES in {m["name"] for m in mf.metrics_for(
            manifest, "per_layer", cell["name"])}, cell["name"]


@pytest.mark.parametrize("rides", [True, False],
                         ids=["the-change", "the-parent"])
def test_a_traced_line_carries_the_share_of_admissions(rides):
    steps = ADMISSIONS if rides else [
        {k: v for k, v in r.items() if k != "admitted"} for r in ADMISSIONS]
    manifest = {"per_layer": [{"name": RIDES, "unit": "%"}], "end_to_end": []}
    collected = {"steps": steps, "sample": [],
                 "correctness": {"ok": True}, "device": {}, "setup_s": 1.0}
    line = bench_run.result_line(manifest, {"name": "any.cell"}, collected,
                                 trace=True)
    assert line["metrics"][RIDES] == {
        "value": pytest.approx(50.0 if rides else 0.0), "unit": "%"}
