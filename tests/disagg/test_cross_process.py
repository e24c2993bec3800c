"""Cross-process handoff, functionally: a `--role prefill` engine commits
the first token(s) over /v1/handoff/prefill, a `--role decode` engine
adopts over /v1/handoff by prompt+committed replay, and the joined stream
is token-identical to one engine serving end-to-end — greedy, seeded, and
grammar-constrained (the FSM cursor is rebuilt by re-walking the committed
tokens on the adopter, docs/disaggregation.md).
"""

import asyncio
import json

import jsonschema
import pytest
from aiohttp.test_utils import TestClient, TestServer

# Two full engine builds (~1 min on a CPU host): excluded from the tier-1
# `-m 'not slow'` sweep. The tier-1 handoff coverage lives in
# test_handoff_wire.py (wire contract) and tests/engine/ (split identity +
# the parameterized preemption suite); this file is the functional
# cross-process proof, run explicitly or in full sweeps.
pytestmark = pytest.mark.slow

from llmlb_tpu.engine.server import create_engine_app
from llmlb_tpu.engine.service import Engine

KW = dict(num_slots=2, slot_capacity=128, prefill_buckets=(16, 32),
          seed=0, kv_page_size=16)

SCHEMA = {
    "type": "object",
    "properties": {"name": {"type": "string", "maxLength": 8},
                   "n": {"enum": [0, 1, 2, 3]}},
    "required": ["name", "n"],
}


@pytest.fixture(scope="module")
def rig():
    async def build():
        pre = Engine.from_preset("debug-tiny", role="prefill", **KW)
        dec = Engine.from_preset("debug-tiny", role="decode", **KW)
        cp = TestClient(TestServer(create_engine_app(pre, owns_engine=False)))
        cd = TestClient(TestServer(create_engine_app(dec, owns_engine=False)))
        await cp.start_server()
        await cd.start_server()
        return pre, dec, cp, cd

    loop = asyncio.new_event_loop()
    pre, dec, cp, cd = loop.run_until_complete(build())
    yield loop, cp, cd, pre, dec
    loop.run_until_complete(cp.close())
    loop.run_until_complete(cd.close())
    pre.shutdown()
    dec.shutdown()
    loop.close()


async def _reference(cp, body) -> dict:
    r = await cp.post("/v1/chat/completions", json=body)
    assert r.status == 200, await r.text()
    return await r.json()


async def _via_handoff(cp, cd, body, *, handoff_tokens=1) -> tuple[dict, dict]:
    """(handoff envelope from the prefill engine, adopted completion)."""
    r = await cp.post("/v1/handoff/prefill",
                      json={**body, "handoff_tokens": handoff_tokens})
    assert r.status == 200, await r.text()
    env = await r.json()
    assert env["object"] == "llmlb.handoff"
    r = await cd.post("/v1/handoff", json={
        "handoff": env["handoff"], "stream": False,
        "tool_name": env.get("tool_name"),
    })
    assert r.status == 200, await r.text()
    return env, await r.json()


def _content(completion: dict) -> str:
    return completion["choices"][0]["message"]["content"]


def test_greedy_adoption_token_identical(rig):
    loop, cp, cd, pre, dec = rig

    async def run():
        body = {"messages": [{"role": "user",
                              "content": "tell me about foxes"}],
                "temperature": 0, "max_tokens": 24}
        ref = await _reference(cp, body)
        env, adopted = await _via_handoff(cp, cd, body)
        assert _content(adopted) == _content(ref)
        assert (adopted["choices"][0]["finish_reason"]
                == ref["choices"][0]["finish_reason"])
        # usage counts committed + continuation as one stream
        assert adopted["usage"] == ref["usage"]
    loop.run_until_complete(run())
    assert pre.core.metrics.handoff_total["emitted"] >= 1
    assert dec.core.metrics.handoff_total["adopted"] >= 1


def test_seeded_adoption_token_identical_with_wider_window(rig):
    loop, cp, cd, _pre, _dec = rig

    async def run():
        body = {"messages": [{"role": "user",
                              "content": "tell me about foxes"}],
                "temperature": 0.9, "seed": 42, "max_tokens": 24}
        ref = await _reference(cp, body)
        _, adopted = await _via_handoff(cp, cd, body, handoff_tokens=5)
        assert _content(adopted) == _content(ref)
    loop.run_until_complete(run())


def test_constrained_adoption_rewalks_the_grammar_cursor(rig):
    """JSON-mode across the wire: the adopter rebuilds the FSM cursor by
    advancing over the committed tokens — a start-state cursor would mask
    the continuation as if at the beginning of the document."""
    loop, cp, cd, _pre, dec = rig

    async def run():
        body = {
            "messages": [{"role": "user", "content": "give me json"}],
            "temperature": 0, "max_tokens": 96,
            "response_format": {"type": "json_schema",
                                "json_schema": {"name": "s",
                                                "schema": SCHEMA}},
        }
        ref = await _reference(cp, body)
        violations = dec.core.metrics.constraint_violations_total
        _, adopted = await _via_handoff(cp, cd, body, handoff_tokens=3)
        assert _content(adopted) == _content(ref)
        jsonschema.validate(json.loads(_content(adopted)), SCHEMA)
        assert dec.core.metrics.constraint_violations_total == violations
    loop.run_until_complete(run())


def test_decode_role_refuses_to_originate(rig):
    loop, _cp, cd, _pre, _dec = rig

    async def run():
        r = await cd.post("/v1/handoff/prefill", json={
            "messages": [{"role": "user", "content": "hi"}],
        })
        assert r.status == 409
    loop.run_until_complete(run())


def test_malformed_payload_is_a_400_not_a_crash(rig):
    loop, _cp, cd, _pre, dec = rig

    async def run():
        r = await cd.post("/v1/handoff", json={
            "handoff": {"version": 1, "prompt_ids": "nope",
                        "committed_ids": [], "sampling": {}},
        })
        assert r.status == 400
        body = await r.json()
        assert "prompt_ids" in body["error"]["message"]
        # the engine still serves after the rejection
        r = await cd.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "still alive?"}],
            "max_tokens": 4, "temperature": 0,
        })
        assert r.status == 200
    loop.run_until_complete(run())


def test_handoff_flight_records_pair_across_processes(rig):
    """Each side of the wire records its half of the handoff — `emitted`
    on the prefill engine, `adopted` on the decode engine — keyed by the
    same gateway request id, with cause stamped before effect, so the
    gateway's `?view=timeline` merge can join them (docs/tracing.md)."""
    loop, cp, cd, _pre, _dec = rig
    rid = "trace-xproc-handoff-1"

    async def run():
        body = {"messages": [{"role": "user",
                              "content": "tell me about wires"}],
                "temperature": 0, "max_tokens": 12}
        r = await cp.post("/v1/handoff/prefill",
                          json={**body, "handoff_tokens": 1},
                          headers={"X-Request-Id": rid})
        assert r.status == 200, await r.text()
        env = await r.json()
        r = await cd.post("/v1/handoff", json={
            "handoff": env["handoff"], "stream": False,
            "tool_name": env.get("tool_name"),
        })
        assert r.status == 200, await r.text()

        r = await cp.get(f"/api/requests/{rid}/timeline")
        assert r.status == 200, await r.text()
        emit_tl = await r.json()
        r = await cd.get(f"/api/requests/{rid}/timeline")
        assert r.status == 200, await r.text()
        adopt_tl = await r.json()
        return emit_tl, adopt_tl

    emit_tl, adopt_tl = loop.run_until_complete(run())
    emitted = [e for e in emit_tl["events"]
               if e["event"] == "handoff_emitted"]
    adopted = [e for e in adopt_tl["events"] if e["event"] == "adopted"]
    assert len(emitted) == 1 and len(adopted) == 1
    # the join key both sides share is the gateway rid (the fixture runs
    # both engines in-process, so the pid-based source tag cannot differ)
    assert emitted[0]["request_id"] == adopted[0]["request_id"] == rid
    assert emitted[0]["ts"] <= adopted[0]["ts"]
    assert adopted[0]["attrs"]["committed"] >= 1


def test_adoption_ships_kv_pages_and_skips_the_replay_prefill(rig):
    """The PR 17 tentpole, cross-process: the prefill engine attaches its
    serialized KV pages to the envelope, the adopter lands them H2D and
    enters decode with ZERO prefill dispatches — the dispatch ledger and
    both engines' kv counters prove the pages moved, and the joined
    timeline shows kv_shipped -> kv_restored with no prefill_chunk on the
    adopter (docs/kv-cache.md)."""
    loop, cp, cd, pre, dec = rig
    rid = "trace-xproc-kvship-1"
    shipped0 = pre.core.metrics.kv_ship_total
    restored0 = dec.core.metrics.kv_restored_total
    fallbacks0 = dict(dec.core.metrics.kv_ship_fallback_total)

    async def run():
        body = {"messages": [{"role": "user",
                              "content": "tell me about page tables"}],
                "temperature": 0, "max_tokens": 24}
        ref = await _reference(cp, body)
        r = await cp.post("/v1/handoff/prefill",
                          json={**body, "handoff_tokens": 3},
                          headers={"X-Request-Id": rid})
        assert r.status == 200, await r.text()
        env = await r.json()
        # the page payload rides INSIDE the handoff block — an old adopter
        # ignores the unknown top-level key and replays as before
        assert "kv_pages" in env["handoff"]
        disp0 = sum(dec.core.prefill_dispatch_by_loop.values())
        r = await cd.post("/v1/handoff", json={
            "handoff": env["handoff"], "stream": False,
            "tool_name": env.get("tool_name"),
        })
        assert r.status == 200, await r.text()
        adopted = await r.json()
        disp = sum(dec.core.prefill_dispatch_by_loop.values()) - disp0
        assert _content(adopted) == _content(ref)
        assert disp == 0, f"adoption ran {disp} replay prefill dispatches"
        r = await cd.get(f"/api/requests/{rid}/timeline")
        assert r.status == 200, await r.text()
        return await r.json()

    adopt_tl = loop.run_until_complete(run())
    assert pre.core.metrics.kv_ship_total == shipped0 + 1
    assert dec.core.metrics.kv_restored_total == restored0 + 1
    assert dict(dec.core.metrics.kv_ship_fallback_total) == fallbacks0
    events = [e["event"] for e in adopt_tl["events"]]
    assert "kv_restored" in events
    assert "prefill_chunk" not in events, (
        "the adopter replay-prefilled despite landing shipped pages"
    )
