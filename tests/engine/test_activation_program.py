"""Activation is one compiled program (`programs._activate_rows`).

A prefilled group enters decode through ONE dispatch that splits the engine's
key, samples every row's first token and scatters the group's rows into the
per-slot device arrays. These tests pin what that program must keep of the
eager sequence it replaced — the tokens, the seed contract of
`ops/sampling.py`, padding rows that touch nothing — and the counter that says
it engaged: one program built per padded group size, none the second time.

Every engine is driven inline (`pending.put`, then `_try_insert()` on the
test's thread), so the requests of one call form one group.
"""

import numpy as np
import pytest

from llmlb_tpu.engine import compilelog
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.engine.tokenizer import ByteTokenizer
from llmlb_tpu.ops.sampling import sample_tokens
from llmlb_tpu.structured import ConstraintCompiler
from tests.support import collect

STATE = ("_d_temps", "_d_top_ps", "_d_top_ks", "_d_seeds", "_d_seq_lens",
         "_d_last_tokens", "_d_lora_idx")
PROGRAM = "jit(_activate_rows)"
PROMPT = [5, 9, 2, 7, 7, 3]
ABC = {"type": "regex", "pattern": "[a-c]+"}  # first token: one of 97..99


@pytest.fixture(scope="module")
def cfg():
    return get_preset("debug-tiny")


def _core(cfg, num_slots=8):
    core = EngineCore(cfg, num_slots=num_slots, slot_capacity=64,
                      prefill_buckets=(16,), seed=0, decode_burst=4)
    core.constraint_compiler = ConstraintCompiler(ByteTokenizer(),
                                                  cfg.vocab_size)
    return core


def _req(prompt=PROMPT, constraint=None, **sampling):
    sampling.setdefault("max_tokens", 4)
    return Request(prompt_ids=list(prompt),
                   sampling=SamplingParams(constraint=constraint, **sampling))


def _state(core):
    return {name: np.asarray(getattr(core, name)) for name in STATE}


def _admit(core, requests):
    """Prefill and activate `requests` as one group. Returns what
    `_activate_group` was handed, the programs the ledger counted inside it,
    and the prefill step's record."""
    seen = {}
    inner = core._activate_group

    def spy(group, slot_ids, lens, logits):
        seen.update(slots=[s for s, _, _ in group], slot_ids=slot_ids,
                    lens=lens, logits=np.asarray(logits))
        before = compilelog.counters().get("programs", 0)
        inner(group, slot_ids, lens, logits)
        seen["programs"] = compilelog.counters().get("programs", 0) - before

    core._activate_group = spy
    try:
        for r in requests:
            core.pending.put(r)
        assert core._try_insert()
    finally:
        del core._activate_group
    assert len(seen["slots"]) == len(requests)  # one group, not several
    seen["record"] = core.step_stats.snapshot(limit=1)["records"][0]
    return seen


def _drain(core, requests, steps=200):
    """Decode inline until every request is done; token streams by request."""
    out = {id(r): [] for r in requests}
    open_ = set(out)
    for _ in range(steps):
        core._decode_active()
        for r in requests:
            tokens, finish = collect(r, timeout=None)  # what is queued
            out[id(r)] += tokens
            if finish is not None:
                open_.discard(id(r))
        if not open_:
            return [out[id(r)] for r in requests]
    raise AssertionError("requests did not finish")


@pytest.mark.parametrize("g", [1, 2, 3])
def test_a_new_padded_group_size_builds_one_program_and_a_second_group_none(
        cfg, g):
    """jit's cache is the process's and keyed by shapes, so each case takes a
    slot count no other engine of this suite has: its padded size is then one
    the process has not seen."""
    core = _core(cfg, num_slots=20 + g)
    first = _admit(core, [_req(temperature=0.0) for _ in range(g)])
    assert first["programs"] == 1
    assert first["record"]["builds"]["names"].count(PROGRAM) == 1
    again = _admit(core, [_req(temperature=0.7) for _ in range(g)])
    assert again["programs"] == 0
    assert PROGRAM not in again["record"]["builds"]["names"]
    # a grammar bias is another input, so another program, once
    biased = _admit(core, [_req(constraint=ABC) for _ in range(g)])
    assert biased["programs"] == 1
    assert biased["record"]["builds"]["names"].count(PROGRAM) == 1
    assert _admit(core, [_req(constraint=ABC)
                         for _ in range(g)])["programs"] == 0


@pytest.mark.parametrize("biased", [False, True], ids=["plain", "bias"])
@pytest.mark.parametrize("g", [1, 2, 3])
def test_activation_writes_the_groups_rows_and_no_other(cfg, g, biased):
    """Row 0 is greedy, row 1 seeded, row 2 draws from the engine's key.
    Greedy rows are the argmax of the prefill's logits (of the allowed
    tokens, under a grammar); the group's slots hold its sampling state;
    every other slot reads as before, padding row or not (3 pads to 4)."""
    core = _core(cfg)
    rows = [dict(temperature=0.0),
            dict(temperature=0.9, top_p=0.8, top_k=5, seed=1234),
            dict(temperature=1.1, top_k=7)][:g]
    constraint = ABC if biased else None
    before = _state(core)
    seen = _admit(core, [_req(constraint=constraint, **kw) for kw in rows])
    after = _state(core)

    slots = seen["slots"]
    assert list(seen["slot_ids"][:g]) == slots
    assert len(seen["slot_ids"]) == (4 if g == 3 else g)
    firsts = after["_d_last_tokens"][slots]
    logits = seen["logits"][:g]
    if biased:
        allowed = np.full(logits.shape[-1], -np.inf, np.float32)
        allowed[97:100] = 0.0
        logits = logits + allowed
        assert set(firsts) <= {97, 98, 99}
    assert firsts[0] == np.argmax(logits[0])

    want = {
        "_d_temps": [kw["temperature"] for kw in rows],
        "_d_top_ps": [kw.get("top_p", 1.0) for kw in rows],
        "_d_top_ks": [kw.get("top_k", 0) for kw in rows],
        "_d_seeds": [kw.get("seed", -1) for kw in rows],
        "_d_seq_lens": [len(PROMPT)] * g,
    }
    for name, values in want.items():
        np.testing.assert_array_equal(
            after[name][slots], np.asarray(values, after[name].dtype), name)
    others = np.setdiff1d(np.arange(core.num_slots), slots)
    for name in STATE:
        np.testing.assert_array_equal(after[name][others],
                                      before[name][others], name)


def test_a_seeded_stream_is_the_same_alone_and_among_unseeded_neighbours(cfg):
    seeded = dict(temperature=0.9, seed=4321, max_tokens=6)
    alone_core = _core(cfg)
    alone = [_req(**seeded)]
    _admit(alone_core, alone)
    (want,) = _drain(alone_core, alone)
    assert len(want) == 6

    core = _core(cfg)
    group = [_req(temperature=1.0, max_tokens=6), _req(**seeded),
             _req(temperature=0.8, max_tokens=6),
             _req([4, 4, 8], temperature=1.2, max_tokens=6)]
    _admit(core, group)
    assert _drain(core, group)[1] == want


def test_the_first_token_folds_the_step_before_the_first_decode_step(cfg):
    """Decode samples with the pre-increment length, so the token after a
    prompt of n folds step n; activation folds n - 1. Four seeded rows over
    one prompt share their logits, so each first token is the reference's
    draw at n - 1 — and the draws at n differ somewhere, so the same step
    folded twice would show."""
    core = _core(cfg)
    seeds = [11, 22, 33, 44]
    seen = _admit(core, [_req(temperature=1.0, seed=s) for s in seeds])
    n = len(PROMPT)

    def draw(step):
        ones = np.ones((4,), np.float32)
        return np.asarray(sample_tokens(
            seen["logits"], core._key, ones, ones, np.zeros((4,), np.int32),
            None, np.asarray(seeds, np.int32), np.full((4,), step, np.int32)))

    firsts = np.asarray(core._d_last_tokens)[seen["slots"]]
    np.testing.assert_array_equal(firsts, draw(n - 1))
    assert (draw(n) != draw(n - 1)).any()
