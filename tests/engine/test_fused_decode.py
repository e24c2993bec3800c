"""Fused decode dispatch (docs/fused-decode.md).

The acceptance bars for the one-program decode step:

- PARITY GRID: over {bf16, int8-KV} x {LoRA on/off} x {spec verify off/on}
  a mixed batch of constrained + free requests, greedy AND seeded, produces
  token-identical streams from a fused engine and a legacy (fused off)
  engine. Interpret-mode CPU JAX, real scheduler.
- ONE DISPATCH: every decode/verify step record on the fused engine counts
  exactly one device program, and constrained slots never force the batch
  into single-step decode (constrained_burst_fallback_total == 0).
- PIN: LLMLB_FUSED_DECODE=0 resolves to the legacy path (and the grid
  proves legacy output unchanged by this PR); default is on.
"""

import numpy as np
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from llmlb_tpu.engine.tokenizer import ByteTokenizer
from llmlb_tpu.lora import save_adapter
from llmlb_tpu.structured import ConstraintCompiler
from tests.support import collect as _drain

CFG = get_preset("debug-tiny")
TOK = ByteTokenizer(CFG.vocab_size)

# repetitive prompt: prompt-lookup speculation finds n-gram matches, so the
# spec legs of the grid actually exercise the verify path
PROMPT = [5, 6, 7, 8, 9] * 5

SCHEMA = {
    "type": "object",
    "properties": {
        "ok": {"type": "boolean"},
        "tag": {"enum": ["alpha", "beta"]},
    },
    "required": ["ok", "tag"],
}


@pytest.fixture(scope="module")
def lora_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("fused_adapters")
    save_adapter(str(d), "acme", CFG, rank=4)
    return str(d)


def _core(*, fused: bool, quant: str | None, lora_dir: str | None,
          spec: bool) -> EngineCore:
    core = EngineCore(
        CFG, num_slots=4, slot_capacity=128, prefill_buckets=(16, 32),
        kv_page_size=16, seed=0, quantize=quant,
        lora_dir=lora_dir, spec_decode=spec, fused_decode=fused,
        eos_id=TOK.eos_id,
    )
    # the service layer normally installs this; the grid drives the raw core
    core.constraint_compiler = ConstraintCompiler(TOK, CFG.vocab_size)
    core.start()
    return core


def _mixed_batch(core: EngineCore, lora: str | None) -> list[list[int]]:
    """Submit the 4-request mixed batch (constrained greedy, constrained
    seeded, free greedy, free seeded) and return the 4 token streams."""
    reqs = [
        Request(prompt_ids=list(PROMPT), sampling=SamplingParams(
            temperature=0.0, max_tokens=24, lora=lora,
            constraint={"type": "json_schema", "schema": SCHEMA})),
        Request(prompt_ids=list(PROMPT), sampling=SamplingParams(
            temperature=0.9, seed=42, max_tokens=24, lora=lora,
            constraint={"type": "json_schema", "schema": SCHEMA})),
        Request(prompt_ids=list(PROMPT), sampling=SamplingParams(
            temperature=0.0, max_tokens=16, lora=lora)),
        Request(prompt_ids=list(PROMPT), sampling=SamplingParams(
            temperature=0.8, seed=7, max_tokens=16, lora=lora)),
    ]
    for r in reqs:
        core.submit(r)
    return [_drain(r)[0] for r in reqs]


GRID = [
    (quant, use_lora, spec)
    for quant in (None, "kv")
    for use_lora in (False, True)
    for spec in (False, True)
]


@pytest.mark.parametrize(
    "quant,use_lora,spec", GRID,
    ids=[f"{'int8kv' if q else 'bf16'}-"
         f"{'lora' if l else 'nolora'}-"
         f"{'spec' if s else 'nospec'}" for q, l, s in GRID])
def test_fused_parity_grid(lora_dir, quant, use_lora, spec):
    """Fused vs legacy token identity over the full feature grid, greedy
    and seeded, constrained and free, in one mixed batch."""
    streams = {}
    for fused in (True, False):
        core = _core(fused=fused, quant=quant,
                     lora_dir=lora_dir if use_lora else None, spec=spec)
        try:
            streams[fused] = _mixed_batch(
                core, "acme" if use_lora else None)
            if fused:
                _assert_fused_invariants(core, spec=spec)
        finally:
            core.stop()
    assert streams[True] == streams[False], (
        f"fused/legacy divergence (quant={quant}, lora={use_lora}, "
        f"spec={spec})")


def _assert_fused_invariants(core: EngineCore, *, spec: bool) -> None:
    # exactly ONE device program per decode/verify step
    records = core.step_stats.snapshot(limit=512)["records"]
    decs = [r for r in records if r["kind"] in ("decode", "verify")]
    assert decs, "no decode steps recorded"
    assert {r["dispatches"] for r in decs} == {1}, decs
    # constrained slots rode the burst: zero single-step fallbacks
    assert core.metrics.constrained_burst_fallback_total == 0
    assert core.metrics.fused_decode_steps_total > 0
    # the grammar actually ran on device
    assert core.metrics.masked_decode_steps_total > 0
    assert core._grammar_tables is not None
    assert core._grammar_tables.schemas_registered >= 1
    assert core._grammar_tables.schemas_rejected == 0
    if spec:
        assert core.metrics.spec_verify_steps_total > 0


# ----------------------------------------------------------- mode resolution


def test_env_pin_and_defaults(monkeypatch):
    """LLMLB_FUSED_DECODE resolves: 0 pins legacy, 1 pins fused, unset
    defaults on."""
    monkeypatch.setenv("LLMLB_FUSED_DECODE", "0")
    core = EngineCore(CFG, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), seed=0)
    assert core.fused_decode is False
    assert core._grammar_tables is None

    monkeypatch.setenv("LLMLB_FUSED_DECODE", "1")
    core = EngineCore(CFG, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), seed=0)
    assert core.fused_decode is True
    assert core._grammar_tables is not None

    monkeypatch.delenv("LLMLB_FUSED_DECODE")
    assert EngineCore(CFG, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), seed=0).fused_decode is True

    # constructor kwarg beats the env var
    monkeypatch.setenv("LLMLB_FUSED_DECODE", "1")
    assert EngineCore(CFG, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), seed=0,
                      fused_decode=False).fused_decode is False


# ----------------------------------------------- transition-table semantics


def test_transition_table_matches_allowed_mask():
    """table[s, v] >= 0 exactly where allowed[s, v] (modulo the dead-end
    EOS escape both sides share), and walking the table replays the host
    DFA token for token."""
    tc = ConstraintCompiler(TOK, CFG.vocab_size).compile_spec(
        {"type": "json_schema", "schema": SCHEMA})
    table = tc.transition_table()
    assert table.shape == tc.allowed.shape
    assert table.dtype == np.int32
    dead = ~tc.allowed.any(axis=1)
    assert ((table[~dead] >= 0) == tc.allowed[~dead]).all()
    for s in np.flatnonzero(dead):
        # dead ends fail open to EOS only — the bias_row deviation, mirrored
        ok = table[s] >= 0
        assert ok[tc.eos_id] and ok.sum() == 1
    # replay: host-side FSM walk == table walk for a valid document
    doc = '{"ok":true,"tag":"alpha"}'
    ids = [ord(c) for c in doc]
    s = 0
    for t in ids:
        assert tc.allowed[s, t], (s, t)
        nxt = int(table[s, t])
        assert nxt >= 0
        s = nxt
    # accepting state: EOS self-loops
    assert int(table[s, tc.eos_id]) == s


def test_grammar_tables_free_row_and_budget():
    from llmlb_tpu.ops.grammar import GrammarTables, grammar_advance, \
        grammar_bias

    tc = ConstraintCompiler(TOK, CFG.vocab_size).compile_spec(
        {"type": "json_schema", "schema": SCHEMA})

    gt = GrammarTables(CFG.vocab_size)
    off = gt.register(tc)
    assert off == 1  # row 0 is the free row
    assert gt.register(tc) == off  # idempotent per instance
    assert gt.rows == 1 + tc.allowed.shape[0]

    # free row: zero bias everywhere, cursor self-loops to 0
    bias = np.asarray(grammar_bias(gt.device(), np.array([0])))
    assert (bias == 0.0).all()
    assert int(np.asarray(
        grammar_advance(gt.device(), np.array([0]), np.array([5])))[0]) == 0

    # a one-row budget rejects registration instead of truncating
    tiny = GrammarTables(CFG.vocab_size,
                         budget_bytes=CFG.vocab_size * 4)
    assert tiny.register(tc) is None
    assert tiny.schemas_rejected == 1
