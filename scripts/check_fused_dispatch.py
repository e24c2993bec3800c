#!/usr/bin/env python
"""Fail when a fused decode step issues more than one device dispatch.

The fused-decode contract (docs/fused-decode.md): with
``LLMLB_FUSED_DECODE=1`` every decode-loop step — including steps where
quantized KV, LoRA, speculative verification and grammar-constrained
sampling are ALL active at once — launches exactly ONE device program.
The scheduler's per-step ledger (StepRecorder ``dispatches`` field +
``decode_dispatch_by_loop``) records what actually launched; this checker
drives a real CPU debug engine with all four features on and fails if any
decode/verify record counts more than one dispatch, if a constrained slot
forced a single-step fallback, or if the feature mix silently didn't
engage (a vacuous pass is a finding too).

Wired as a tier-1 test (tests/test_fused_dispatch.py); standalone:

    python scripts/check_fused_dispatch.py
"""

from __future__ import annotations

import os
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

SCHEMA = {
    "type": "object",
    "properties": {
        "ok": {"type": "boolean"},
        "tag": {"enum": ["alpha", "beta"]},
    },
    "required": ["ok", "tag"],
}

# repetitive prompt so prompt-lookup speculation actually drafts
PROMPT = [5, 6, 7, 8, 9] * 5


def _drain(request):
    from llmlb_tpu.engine.scheduler import event_tokens

    toks = []
    while True:
        kind, val = request.events.get(timeout=120)
        if kind == "done":
            return toks
        if kind == "error":
            raise RuntimeError(f"engine error: {val}")
        toks.extend(event_tokens(kind, val))


def run_check() -> list[str]:
    """Drive the 4-feature-on batch; return human-readable findings."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    prior = os.environ.get("LLMLB_FUSED_DECODE")
    os.environ["LLMLB_FUSED_DECODE"] = "1"
    sys.path.insert(0, str(REPO))
    try:
        return _run_check_inner()
    finally:
        # in-process callers (tests/test_fused_dispatch.py) must not leak
        # the forced mode into the rest of the pytest session
        if prior is None:
            del os.environ["LLMLB_FUSED_DECODE"]
        else:
            os.environ["LLMLB_FUSED_DECODE"] = prior


def _run_check_inner() -> list[str]:

    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import EngineCore, Request, \
        SamplingParams
    from llmlb_tpu.engine.tokenizer import ByteTokenizer
    from llmlb_tpu.lora import save_adapter
    from llmlb_tpu.structured import ConstraintCompiler

    cfg = get_preset("debug-tiny")
    tok = ByteTokenizer(cfg.vocab_size)
    with tempfile.TemporaryDirectory() as lora_dir:
        save_adapter(lora_dir, "acme", cfg, rank=4)
        core = EngineCore(
            cfg, num_slots=4, slot_capacity=128, prefill_buckets=(16, 32),
            kv_page_size=16, seed=0,
            quantize="kv", lora_dir=lora_dir, spec_decode=True,
            eos_id=tok.eos_id,
        )
        core.constraint_compiler = ConstraintCompiler(tok, cfg.vocab_size)
        core.start()
        try:
            findings: list[str] = []
            if not core.fused_decode:
                return ["LLMLB_FUSED_DECODE=1 did not enable fused decode"]
            reqs = [
                # plain greedy
                Request(prompt_ids=list(PROMPT), sampling=SamplingParams(
                    temperature=0.0, max_tokens=16)),
                # LoRA seeded
                Request(prompt_ids=list(PROMPT), sampling=SamplingParams(
                    temperature=0.8, seed=7, max_tokens=16, lora="acme")),
                # JSON-constrained greedy, riding the same batch
                Request(prompt_ids=list(PROMPT), sampling=SamplingParams(
                    temperature=0.0, max_tokens=24,
                    constraint={"type": "json_schema", "schema": SCHEMA})),
                # JSON-constrained + LoRA, seeded
                Request(prompt_ids=list(PROMPT), sampling=SamplingParams(
                    temperature=0.9, seed=42, max_tokens=24, lora="acme",
                    constraint={"type": "json_schema", "schema": SCHEMA})),
            ]
            for r in reqs:
                core.submit(r)
            for r in reqs:
                _drain(r)

            records = core.step_stats.snapshot(limit=512)["records"]
            decs = [r for r in records
                    if r["kind"] in ("decode", "verify")]
            if not decs:
                findings.append("no decode/verify steps recorded")
            multi = [r for r in decs if r["dispatches"] != 1]
            for r in multi:
                findings.append(
                    f"step seq={r['seq']} kind={r['kind']} launched "
                    f"{r['dispatches']} device dispatches (want 1)")
            m = core.metrics
            if m.constrained_burst_fallback_total:
                findings.append(
                    f"{m.constrained_burst_fallback_total} constrained "
                    "single-step fallback(s) — grammar not device-resident")
            # the feature mix must have engaged, else the pass is vacuous
            if m.masked_decode_steps_total == 0:
                findings.append("no grammar-masked decode steps ran")
            if m.spec_verify_steps_total == 0:
                findings.append("no speculative verify steps ran")
            if m.fused_decode_steps_total == 0:
                findings.append("no fused decode steps counted")
            gt = core._grammar_tables
            if gt is None or gt.schemas_registered == 0:
                findings.append("no schema registered in grammar tables")
            elif gt.schemas_rejected:
                findings.append(
                    f"{gt.schemas_rejected} schema(s) rejected by the "
                    "grammar-table budget")
            total = sum(core.decode_dispatch_by_loop.values())
            if total != len(decs):
                findings.append(
                    f"dispatch ledger {total} != decode/verify step "
                    f"count {len(decs)}")
            return findings
        finally:
            core.stop()


def main() -> int:
    findings = run_check()
    for what in findings:
        print(what, file=sys.stderr)
    if findings:
        print(f"\n{len(findings)} fused-dispatch violation(s) found",
              file=sys.stderr)
        return 1
    print("every decode step under LLMLB_FUSED_DECODE=1 launched exactly "
          "one device program")
    return 0


if __name__ == "__main__":
    sys.exit(main())
