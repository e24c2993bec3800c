"""The cases every family's serving is held to, each written ONCE.

A family's file (`tests/engine/test_*_family.py`) states its `CASE` — the
record below: its debug preset, the published config the preset must equal,
its plain reference (`benchmark/reference/`), the lengths that cross its
mechanism's boundary, its one-term controls, what it refuses by name — and
imports the functions here that it has the record for. `conftest.py` runs
each for the importing file's family (`case`) and once an entry of the
record's list the function's other argument names (`run`, `control`,
`refusal`, `start`); the ids are the family's name and the entry's. What a
family's mechanism alone has stays a test of its own file.

On the CPU at a small size, float32, seeded weights. An `EngineCore` is
built once a file (`engine`) and shared by every case at the engine; the
reference's whole-sequence pass is made at ONE padded length a family
(`reference_logits`: its layers are jitted a length).
"""

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable, NamedTuple

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_limits, correctness
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.models import config_from_hf, family_for
from llmlb_tpu.models.llama import StatePool
from tests.support import collect_events

MARGIN = 1e-3  # of the reference's top two logits: wider than rounding


class Control(NamedTuple):
    """A program with one term wrong, as `correctness.check` is handed it."""

    served: Any  # the family's module, or a variant of benchmark/check_*.py
    cfg: Any
    params: dict  # what the program is given
    reference: Any
    patch: Callable = contextlib.nullcontext  # held while the programs trace
    ground: str = "logits"  # of the refusal (correctness.check's `grounds`)


class Shares(NamedTuple):
    """A mixture layer cut into the shares of its chips."""

    uncut: Any  # the reference's layer with every expert [T, E]
    # a chip: (the program's, the reference's) part that this chip alone adds
    parts: list
    whole: Callable  # the sum of the parts -> the layer, the rest once
    atol: float = 1e-5


class State(NamedTuple):
    """A pool that holds, beside pages, a state a slot."""

    slot_axis: tuple[int, int]  # of cache_k.state and cache_v.state
    atol: float
    # (cfg, live rows, their K/V cells) -> {counter, or a sum "a+b": value}
    counters: Callable
    pool: Callable  # (cfg, cache_k, cache_v of 5 pages, 3 slots) -> pairs


class Ring(NamedTuple):
    """A state a slot that holds the last cells of a window."""

    slot: Callable  # (cache_k.state, slot) -> what that slot holds of it
    decode_to: int  # the short request's length: past the window
    counters: Callable  # (cells the row holds) -> {counter: value} of a step


class Engine(NamedTuple):
    args: dict  # EngineCore's
    requests: tuple  # (prompt tokens, max_tokens), submitted all at once
    records: Callable  # (case, core, records of the requests): asserts
    refused_starts: tuple  # (EngineCore keywords, the refusal's words)
    long_beside_short: tuple = ()  # max_tokens of the rows of 400 and 40


@dataclasses.dataclass(frozen=True)
class Case:
    family: Any  # llmlb_tpu.models.<module>
    preset: str
    hf: dict  # the published config.json at the preset's size
    reference: Any = None
    page: int = 16
    # correctness.check's: lengths that cross the mechanism's boundary
    spec: dict = dataclasses.field(default_factory=dict)
    # (id, changes to spec, seed) of the comparisons with the reference
    runs: tuple = (("seed3", {}, 3),)
    tolerance: float = 1e-5  # on max_rel_rms_err, at or under spec's own
    # fields in which the debug preset departs from the config read
    preset_departs: dict = dataclasses.field(default_factory=dict)
    reads: Callable = lambda cfg: []  # -> (got, want) pairs of the config
    controls: dict = dataclasses.field(default_factory=dict)  # -> Control
    control_fails_by: float = 1e-3
    # changes to spec for the controls: no extend where every control shows
    # in a prefill and decode steps (a program fewer to compile a control)
    control_spec: dict = dataclasses.field(default_factory=dict)
    refused: tuple = ()  # (changes to hf, the key the refusal names)
    shares: Callable | None = None  # () -> Shares
    state: State | None = None
    ring: Ring | None = None
    engine: Engine | None = None
    padded: int = 128  # the ONE length the reference's pass is made at
    atol: float = 1e-5  # logits against the reference's, by position

    @property
    def name(self) -> str:
        return self.family.FAMILY.name

    @functools.cached_property
    def cfg(self):
        return get_preset(self.preset)

    def control(self, params, served=None, cfg=None, given=None, **kw):
        """A Control whose reference passes over the TRUE weights, whatever
        the program is given."""
        def on_true(_given, hf, ids, **kwargs):
            return self.reference.forward(params, hf, ids, **kwargs)

        reference = (self.reference if given is None
                     else check_limits.like(self.reference, on_true))
        return Control(served or self.family, cfg or self.cfg,
                       params if given is None else given, reference, **kw)


# --- helpers -----------------------------------------------------------------

@contextlib.contextmanager
def patched(obj, name, value):
    """`obj.name` is `value` while a control's programs trace."""
    real = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, real)


def ids(case, n, seed=0):
    return np.random.default_rng(seed).integers(0, case.cfg.vocab_size, n)


def prompt(n, seed):
    return np.random.default_rng(seed).integers(8, 500, size=n).tolist()


def pool(case, pages, slots=1):
    return case.family.init_kv_pages(case.cfg, pages + 1, case.page,
                                     num_slots=slots)


def table(pages, rows=1):
    return jnp.asarray(1 + np.arange(rows * pages, dtype=np.int32)
                       .reshape(rows, pages))


def reference_logits(case, params, tokens):
    """The reference's logits [T, V] of `tokens`, by one whole-sequence
    pass at the family's padded length (the pass is causal: what follows a
    position does not reach it)."""
    padded = np.zeros(case.padded, np.int32)
    padded[:len(tokens)] = tokens
    out = case.reference.forward(params, case.hf, padded)
    logits = out[0] if isinstance(out, tuple) else out  # beside the router's
    return np.asarray(logits)[:len(tokens)]


def assert_greedy(case, params, prompt_ids, tokens):
    """`tokens` are the reference's greedy tokens after the prompt: the
    largest logit of ONE pass over prompt + tokens at every position from
    the prompt's last on (by induction over the tokens, what a pass a token
    would give), wherever its top two logits are not a tie."""
    rows = reference_logits(case, params, prompt_ids + tokens)[
        len(prompt_ids) - 1:-1]
    top = np.sort(rows, axis=-1)
    wide = top[:, -1] - top[:, -2] > MARGIN
    assert wide.sum() >= len(tokens) - 2
    assert (np.argmax(rows, -1)[wide] == np.asarray(tokens)[wide]).all(), (
        len(prompt_ids), tokens, np.argmax(rows, -1).tolist())


def submit(core, prompt_ids, max_tokens, **sampling):
    return core.submit(Request(prompt_ids=prompt_ids, sampling=SamplingParams(
        max_tokens=max_tokens, temperature=0.0, **sampling)))


def steps_so_far(core) -> int:
    return core.step_stats.snapshot(limit=0)["steps_total"]


def records_since(core, mark: int) -> list[dict]:
    snap = core.step_stats.snapshot(limit=512)
    return snap["records"][:snap["steps_total"] - mark]


def served_is_the_reference(case, core, params, requests):
    """Submit (prompt, max_tokens) all at once; every stream ends by its
    length and is the reference's. Returns the step records they left."""
    mark = steps_so_far(core)
    sent = [(p, n, submit(core, p, n)) for p, n in requests]
    for prompt_ids, n, request in sent:
        tokens, reason, _ = collect_events(request, 600)
        assert reason == "length" and len(tokens) == n
        assert_greedy(case, params, prompt_ids, tokens)
    return records_since(core, mark)


def prefill_rows(case, params, rows, lens, slots, pages, width):
    """One prefill of `rows` (padded to `width`) into `slots`, two pages a
    slot."""
    padded = np.zeros((len(rows), width), np.int32)
    for i, row in enumerate(rows):
        padded[i, :len(row)] = row
    tables = jnp.asarray([[1 + 2 * s, 2 + 2 * s] for s in slots], jnp.int32)
    return case.family.prefill_into_pages(
        params, case.cfg, jnp.asarray(padded), jnp.asarray(lens, jnp.int32),
        tables, *pages, None, slot_ids=jnp.asarray(slots, jnp.int32))


def _slot(state, axis, i):
    return np.take(np.asarray(state), i, axis=axis)


def _counted(counters, want: dict) -> dict:
    return {name: sum(int(counters[n]) for n in name.split("+"))
            for name in want}


# --- an engine of a family that holds a state a slot -----------------------

def state_records(case, core, recs):
    """What the step records of an engine that holds a state a slot must
    say."""
    attention_layers = case.family.kv_pool_layers(case.cfg)
    decodes = [r for r in recs if r["kind"] == "decode"]
    prefills = [r for r in recs if r["kind"] == "prefill"]
    assert decodes and prefills
    for r in decodes:  # rows x steps of the burst, the live rows alone
        assert r["state_rows"] == r["tokens"]
        assert r["global_kv_tokens"] >= r["tokens"] * attention_layers * 5
        assert "scan_tokens" not in r
    for r in prefills:
        assert r["scan_tokens"] == r["tokens"] and r["scan_chunks"] >= 1
    assert any(r["tokens"] == 32 for r in prefills), "no chunk recorded"
    m = core.metrics.summary()
    assert m["ssm_state_rows_total"] >= sum(r["state_rows"] for r in recs)
    assert m["global_kv_tokens_total"] > 0


def state_engine(pool_words):
    """Seven requests on four slots: 70 and 40 tokens prefill in chunks of
    32 while other rows decode in bursts of 4, the short ones are admitted
    as a group, and the fifth to seventh take a slot another request's
    state was left in."""
    return Engine(
        args=dict(num_slots=4, slot_capacity=128, prefill_buckets=(16, 32),
                  kv_page_size=16, decode_burst=4, eos_id=-1),
        requests=tuple((prompt(n, 10 + n), 12)
                       for n in (17, 40, 5, 70, 33, 20, 9)),
        records=state_records,
        refused_starts=(
            (dict(prefix_cache=True), "the prefix cache"),
            (dict(spec_decode=True), "speculative decoding"),
            (dict(quantize="kv"), pool_words),
            (dict(quantize="weights"), "does not serve int8 weights")))


# --- the configuration -------------------------------------------------------

def test_the_preset_is_the_published_config_read(case):
    cfg = config_from_hf(case.hf, jnp.float32)
    assert dataclasses.replace(cfg, **case.preset_departs) == case.cfg
    assert family_for(cfg) is case.family
    for i, (got, want) in enumerate(case.reads(cfg)):
        assert got == want, (i, got, want)


def test_what_the_family_does_not_compute_is_refused_by_name(case, refusal):
    change, named = refusal
    with pytest.raises(NotImplementedError, match=named):
        config_from_hf({**case.hf, **change}, jnp.float32)


# --- against the reference ---------------------------------------------------

def test_prefill_extend_decode_match_the_reference_at_every_position(
        case, params, run):
    """The family's prefill -> extend chunks -> decode steps through its
    pool against the reference's one forward pass, the routing followed
    where the reference follows one."""
    _, changes, seed = run
    spec = {**case.spec, **changes}
    out = correctness.check(case.family, case.cfg, params, case.hf, spec,
                            seed, case.page, case.reference)
    assert out["ok"] and out.get("grounds", []) == [], out
    assert out["max_rel_rms_err"] < case.tolerance, out
    assert out["positions_compared"] == (
        1 + spec["extend_chunks"] + spec["decode_steps"])
    if "router_rel_rms_err" in out:
        assert out["router_rel_rms_err"] < 1e-5
        assert out["dropped_assignments"] == 0 and out["choice_is_own_topk"]


def test_a_program_with_one_term_wrong_fails_the_comparison(
        case, params, control):
    c = case.controls[control](params)
    with c.patch():
        out = correctness.check(c.served, c.cfg, c.params, case.hf,
                                {**case.spec, **case.control_spec}, 3,
                                case.page, c.reference)
    assert not out["ok"], out
    assert c.ground in out.get("grounds", ["logits"]), out
    if c.ground == "logits":
        assert out["max_rel_rms_err"] > case.control_fails_by, out


def test_the_shares_add_up_to_the_uncut_layer(case):
    """Each chip's part is the reference's part of that share, and the
    parts of every chip, with what the chips compute alike counted ONCE,
    are the reference's uncut layer."""
    shares = case.shares()
    for got, want in shares.parts:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=shares.atol)
    total = sum(want for _, want in shares.parts)
    np.testing.assert_allclose(np.asarray(shares.whole(total)),
                               np.asarray(shares.uncut), atol=3 * shares.atol)


def test_rows_of_40_and_400_tokens_decode_in_one_step(case, params):
    """Two rows far apart in length, prefilled as a group of unlike lengths
    through chunks, then decoded together: each row's logits are its own
    sequence's."""
    rows = [ids(case, 44, 7), ids(case, 404, 8)]
    want = [reference_logits(case, params, row) for row in rows]
    pages = -(-(400 + 8) // case.page)
    ck, cv = pool(case, 2 * pages, slots=2)
    tables = table(pages, rows=2)
    lens, start = np.asarray([40, 400]), np.zeros(2, np.int32)
    while (start < lens).any():  # chunks of 64, the rows at their own pace
        n = np.minimum(lens - start, 64)
        chunk = np.zeros((2, 64), np.int32)
        for r in range(2):
            chunk[r, :n[r]] = rows[r][start[r]:start[r] + n[r]]
        _, ck, cv, _ = case.family.prefill_extend_pages(
            params, case.cfg, jnp.asarray(chunk), jnp.asarray(n),
            jnp.asarray(start), tables, ck, cv, slot_ids=jnp.asarray([0, 1]))
        start = start + n
    for step in range(4):
        pos = lens + step
        logits, ck, cv, _ = case.family.decode_step_paged(
            params, case.cfg,
            jnp.asarray([rows[r][pos[r]] for r in range(2)]),
            jnp.asarray(pos), ck, cv, tables, window=pages * case.page)
        for r in range(2):
            np.testing.assert_allclose(np.asarray(logits[r]),
                                       want[r][pos[r]], atol=case.atol)


def test_a_slot_taken_by_a_shorter_request_sees_nothing_of_its_predecessor(
        case, params):
    """A prompt of 40 fills slot 0's ring; a prompt of 5 prefilled into the
    same slot (cells >= 5 still hold the other's keys) decodes as if the
    ring were fresh; a row that is not live beside it writes the trash ring
    and leaves slot 1's ring as it was. The counters are the cells (and the
    pages) the step's masks (its work-lists) named."""
    family, cfg, ring = case.family, case.cfg, case.ring
    long_ids, short_ids = ids(case, 40, 5), ids(case, ring.decode_to, 6)
    ck, cv = pool(case, 16, slots=2)
    tables = table(8, rows=2)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :40] = long_ids
    for slot in (0, 1):
        _, ck, cv, _ = family.prefill_into_pages(
            params, cfg, jnp.asarray(padded), jnp.asarray([40]),
            tables[slot:slot + 1], ck, cv, slot_ids=jnp.asarray([slot]))
    other = np.asarray(ring.slot(ck.state, 1))
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = short_ids[:5]
    logits, ck, cv, _ = family.prefill_into_pages(
        params, cfg, jnp.asarray(padded), jnp.asarray([5]), tables[:1], ck,
        cv, slot_ids=jnp.asarray([0]))
    want = reference_logits(case, params, short_ids)
    np.testing.assert_allclose(np.asarray(logits[0]), want[4], atol=case.atol)
    live = jnp.asarray([True, False])
    for pos in range(5, ring.decode_to):
        logits, ck, cv, counters = family.decode_step_paged(
            params, cfg, jnp.asarray([short_ids[pos], 9]),
            jnp.asarray([pos, 127]), ck, cv, tables, window=64, live=live)
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos],
                                   atol=case.atol)
        assert _counted(counters, stepped := ring.counters(pos + 1)) == stepped
    np.testing.assert_array_equal(np.asarray(ring.slot(ck.state, 1)), other)


# --- the state's life --------------------------------------------------------

def test_a_padded_bucket_leaves_the_state_of_the_true_prompt(case, params):
    a = ids(case, 21, 1).tolist()
    exact, ck, cv, _ = prefill_rows(case, params, [a], [21], [0],
                                    pool(case, 8), 21)
    padded, pk, pv, _ = prefill_rows(case, params, [a], [21], [0],
                                     pool(case, 8), 32)
    for got, want in ((padded, exact), (pk.state, ck.state),
                      (pv.state, cv.state)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=case.state.atol)
    assert np.abs(np.asarray(ck.state)).max() > 0


def test_a_repeated_row_and_a_used_slot_write_the_state_of_their_prompt(
        case, params):
    """A prefill group padded by repeating its last row (both write slot
    1), into a pool whose slots hold another request's state: what slot 1
    holds afterwards is its prompt's alone (a fresh sequence voids what the
    slot held), and slot 2 is untouched."""
    k_axis, v_axis = case.state.slot_axis
    a, b = ids(case, 21, 1).tolist(), ids(case, 13, 2).tolist()
    _, want_k, want_v, _ = prefill_rows(case, params, [b], [13], [0],
                                        pool(case, 8), 16)
    ck, cv = pool(case, 8, slots=3)
    ck = ck._replace(state=ck.state + 3.0)  # what a finished request left
    cv = cv._replace(state=cv.state - 2.0)
    _, ck, cv, counters = prefill_rows(
        case, params, [a, b, b], [21, 13, 13], [0, 1, 1], (ck, cv), 32)
    np.testing.assert_allclose(_slot(ck.state, k_axis, 1),
                               _slot(want_k.state, k_axis, 0),
                               atol=case.state.atol)
    np.testing.assert_allclose(_slot(cv.state, v_axis, 1),
                               _slot(want_v.state, v_axis, 0),
                               atol=case.state.atol)
    assert (_slot(ck.state, k_axis, 2) == 3.0).all()
    assert (_slot(cv.state, v_axis, 2) == -2.0).all()
    want = {"scan_tokens": 47, "scan_chunks": 3 * 2,
            **case.state.counters(case.cfg, 3, 47)}
    assert _counted(counters, want) == want


def test_a_decode_step_advances_the_live_rows_alone(case, params):
    """A step with row 1 not live (a slot mid-way through a chunked
    prefill, or free): its state and its carried rows stay bit for bit,
    row 0's move, and row 0's logits are what a step with every row live
    gives."""
    k_axis, v_axis = case.state.slot_axis
    a, b = ids(case, 21, 1).tolist(), ids(case, 13, 2).tolist()
    _, ck, cv, _ = prefill_rows(case, params, [a, b], [21, 13], [0, 1],
                                pool(case, 8, slots=2), 32)
    before_k, before_v = np.asarray(ck.state), np.asarray(cv.state)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    args = (jnp.asarray([5, 6], jnp.int32), jnp.asarray([21, 13], jnp.int32))

    def step(live):
        k = StatePool(ck.pages + 0, ck.state + 0)
        v = StatePool(cv.pages + 0, cv.state + 0)
        return case.family.decode_step_paged(
            params, case.cfg, *args, k, v, tables, None, window=32,
            live=None if live is None else jnp.asarray(live))

    logits, k, v, counters = step([True, False])
    assert (_slot(k.state, k_axis, 1) == _slot(before_k, k_axis, 1)).all()
    assert (_slot(v.state, v_axis, 1) == _slot(before_v, v_axis, 1)).all()
    assert (_slot(k.state, k_axis, 0) != _slot(before_k, k_axis, 0)).any()
    assert (_slot(v.state, v_axis, 0) != _slot(before_v, v_axis, 0)).any()
    want = case.state.counters(case.cfg, 1, 22)
    assert _counted(counters, want) == want
    both, k2, _v2, counters = step(None)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(both[0]),
                               atol=max(case.state.atol, 1e-5))
    assert (_slot(k2.state, k_axis, 1) != _slot(before_k, k_axis, 1)).any()
    want = case.state.counters(case.cfg, 2, 22 + 14)
    assert _counted(counters, want) == want


def test_the_pool_holds_pages_of_the_attention_layers_and_state_per_slot(
        case):
    family, cfg = case.family, case.cfg
    ck, cv = family.init_kv_pages(cfg, 5, case.page, num_slots=3)
    for i, (got, want) in enumerate(case.state.pool(cfg, ck, cv)):
        assert got == want, (i, got, want)
    assert ck.state.dtype == jnp.float32
    one = family.init_kv_pages(cfg, 5, case.page)[0]
    assert one.state.shape[case.state.slot_axis[0]] == 1  # serves one row
    assert family.kv_wire_cell(cfg) is None
    assert not hasattr(family, "verify_step_paged")


# --- through the continuous-batching engine ----------------------------------

def test_the_engines_tokens_are_the_references_greedy_tokens(
        case, engine, params):
    """More requests than slots, all at once: the long prompts prefill in
    chunks while other rows decode in bursts (a burst steps every slot: the
    prefilling slot's state must stay), and the later ones take a slot
    another request's state was left in. The step records carry the
    family's counters."""
    records = served_is_the_reference(case, engine, params,
                                      case.engine.requests)
    case.engine.records(case, engine, records)
    slots = case.engine.args["num_slots"]
    assert engine.quant_info()["state_bytes"] == (
        slots * case.family.state_slot_bytes(case.cfg))


def test_rows_of_40_and_400_tokens_share_the_engines_steps(
        case, engine, params):
    """A prompt of 400 tokens (chunks of 32 through the extend path) beside
    one of 40: once both decode, every burst steps a row at a context of
    400 and one at 40; both streams are the reference's."""
    requests = zip((prompt(400, 90), prompt(40, 91)),
                   case.engine.long_beside_short)
    records = served_is_the_reference(case, engine, params, list(requests))
    layers = case.family.kv_pool_layers(case.cfg)  # that read every cell
    both = [r for r in records if r["kind"] == "decode"
            and r["active_slots"] == 2
            and r["global_kv_tokens"] >= layers * 400 * (r["tokens"] // 2)]
    assert both, "no burst stepped the long row and the short one together"


def test_park_and_resume_is_token_identical(case, engine, params):
    """Every slot taken by a low-priority request: one parks mid-generation
    for a high-priority arrival and resumes by replaying prompt + tokens
    through prefill and extend (nothing of its state is kept); every stream
    is the reference's."""
    parked = engine.metrics.preemptions_total
    victims = [(p, submit(engine, p, 40, priority=2))
               for p in (prompt(20 + i, 80 + i)
                         for i in range(len(engine.slots)))]
    deadline = time.monotonic() + 120
    while (min(s.generated for s in engine.slots) < 6
           and time.monotonic() < deadline):
        time.sleep(0.005)
    other_prompt = prompt(9, 89)
    other = submit(engine, other_prompt, 7, priority=0)
    got_other, _, _ = collect_events(other, 600)
    assert_greedy(case, params, other_prompt, got_other)
    for victim_prompt, victim in victims:
        tokens, reason, _ = collect_events(victim, 600)
        assert reason == "length" and len(tokens) == 40
        assert_greedy(case, params, victim_prompt, tokens)
    assert engine.metrics.preemptions_total > parked


def test_an_engine_that_would_serve_the_family_wrong_does_not_start(
        case, params, start):
    kw, message = start
    with pytest.raises(NotImplementedError, match=message):
        EngineCore(case.cfg, params, **{**case.engine.args, **kw})
