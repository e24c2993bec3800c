"""A decode dispatch hands the attention kernel the rows that decode.

The Pallas decode kernel walks a work-list of the live rows' pages
(`ops/pallas_attention.decode_work_list`), and which rows are live is the
scheduler's knowledge: the device's length counters keep counting for a freed
and for a never-used slot, and a prefilling slot is parked at capacity - 1.
One engine here holds all three kinds beside two decoding rows; its greedy
tokens on the Pallas route (the interpreter, on the CPU) are those of the XLA
route, and its decode records count the pages that were live against the
pages of slots x window.

Every engine is driven inline (`pending.put`, then the loop's own calls on
the test's thread), so the steps and their order are the test's.
"""

import dataclasses

import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.ops.attention import traced_routes
from tests.support import collect

SLOTS, CAPACITY, PAGE, BURST = 6, 512, 16, 4
WINDOW = 256  # the smallest bucket; every context here stays under it
PROMPTS = {  # name -> (prompt length, tokens asked for)
    "a": (6, 14), "gone1": (9, 2), "gone2": (4, 2), "b": (13, 11),
    "chunked": (40, 6),  # over the one bucket of 16: three extend chunks
}


def _request(name):
    n, max_tokens = PROMPTS[name]
    prompt = [(7 * i + 3 * len(name)) % 251 + 1 for i in range(n)]
    return Request(prompt_ids=prompt, sampling=SamplingParams(
        temperature=0.0, max_tokens=max_tokens))


def _tokens(request):
    return collect(request, timeout=None)[0]  # what is queued


def _serve(monkeypatch, route):
    """The scenario on one attention route. Returns the token streams by
    name and the decode record of the step that held every kind of row."""
    # The route is read while a program is traced, and jax keeps a traced
    # program by its static arguments: a configuration of its own per route
    # (a length limit no slot reaches) is a program of its own per route.
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", route)
    cfg = dataclasses.replace(
        get_preset("debug-tiny"),
        max_position_embeddings=CAPACITY + ("xla", "pallas").index(route))
    core = EngineCore(cfg, num_slots=SLOTS, slot_capacity=CAPACITY,
                      prefill_buckets=(16,), kv_page_size=PAGE, seed=0,
                      decode_burst=BURST, prefix_cache=False)
    reqs = {name: _request(name) for name in PROMPTS}
    for name in ("a", "gone1", "gone2", "b"):  # slots 0..3, one group
        core.pending.put(reqs[name])
    assert core._try_insert()
    assert core._decode_active()  # gone1 and gone2 finish: slots 1, 2
    assert [s.request is None for s in core.slots] == [
        False, True, True, False, True, True]

    core.pending.put(reqs["chunked"])  # claims slot 1, the lowest free
    core._try_insert()
    assert core._advance_prefill()  # 16 of its 40 tokens are in
    assert core.slots[1].prefilling and core.slots[1].prefill_pos == 16
    # slot 0 and 3 decode; 1 is prefilling with pages of its own; 2 was
    # freed and keeps counting; 4 and 5 were never used
    lens = [int(core._seq_lens[i]) for i in (0, 3)]
    assert core._decode_active()
    record = core.step_stats.snapshot(limit=1)["records"][0]
    assert record["kind"] == "decode" and record["active_slots"] == 2
    assert record["kv_pages_live"] == sum(
        -(-(n + BURST) // PAGE) for n in lens)

    for _ in range(40):
        core._advance_prefill()
        if not core._decode_active():
            break
    else:
        raise AssertionError("requests did not finish")
    return {name: _tokens(r) for name, r in reqs.items()}, record


def test_free_unused_and_prefilling_rows_beside_decoding_ones(monkeypatch):
    xla, _ = _serve(monkeypatch, "xla")
    assert traced_routes()["paged_decode"] == "xla"
    pallas, record = _serve(monkeypatch, "pallas")
    assert traced_routes()["paged_decode"] == "pallas:paged_flash_decode"

    assert {name: len(toks) for name, toks in pallas.items()} == {
        name: max_tokens for name, (_, max_tokens) in PROMPTS.items()}
    assert pallas == xla
    # rows 0 and 3 held their prompts and the first burst, 6 + 4 and 13 + 4
    # cells; with this burst's 4 that is 14 and 21: one page of 16 and two,
    # of the 16 pages the window's sweep gives each of the six slots
    assert record["kv_pages_live"] == 1 + 2
    assert record["kv_pages_window"] == SLOTS * WINDOW // PAGE == 96


@pytest.mark.parametrize("burst", [1, 4])
def test_decode_records_and_totals_count_live_and_window_pages(burst):
    """Both decode paths (the burst program and the single step) put the two
    counts on their record and into the engine's running totals."""
    core = EngineCore(get_preset("debug-tiny"), num_slots=3,
                      slot_capacity=64, prefill_buckets=(16,),
                      kv_page_size=8, seed=0, decode_burst=burst,
                      fused_decode=False, prefix_cache=False)
    core.pending.put(Request(prompt_ids=list(range(1, 12)),
                             sampling=SamplingParams(temperature=0.0,
                                                     max_tokens=8)))
    assert core._try_insert()
    live = window = 0
    held = 11  # cells in the row's pages before a dispatch
    while core._decode_active():
        record = core.step_stats.snapshot(limit=1)["records"][0]
        assert record["kv_pages_live"] == -(-(held + burst) // 8)
        assert record["kv_pages_window"] == 3 * 64 // 8
        assert record["kv_pages_live"] <= record["kv_pages_window"]
        live += record["kv_pages_live"]
        window += record["kv_pages_window"]
        held += burst
    totals = core.metrics.summary()
    assert totals["decode_kv_pages_live_total"] == live > 0
    assert totals["decode_kv_pages_window_total"] == window
