"""KV page shipping + tiered host-RAM offload (docs/kv-cache.md).

Three layers, mirroring the implementation split:
- kv_transfer unit tests: compat gating order, and every structural lie a
  payload can tell (bad magic, truncation, trailing bytes, geometry
  mismatch) raises KVTransferError — the callers count a labeled fallback
  and replay; a bad payload is never a client-visible error. The header
  round-trip auto-probe lives with the handoff wire tests
  (tests/disagg/test_handoff_wire.py).
- KVOffloadTier unit tests (pure host): budget/LRU math, the
  longer-entry-matches-on-its-head rule, parked pop/drop.
- EngineCore integration (CPU backend): a preempted request restores its
  parked pages from the host tier and continues token-identically —
  greedy AND seeded, bf16 AND int8 pools — with ZERO prefill dispatches
  for the resume (the dispatch ledger proves it); a prefix entry evicted
  under page pressure re-hits from the tier; and with both knobs off the
  engine is bit-identical to the replay-only behavior it ships today.
"""

import numpy as np
import pytest

from llmlb_tpu.engine.kv_offload import KVOffloadTier
from llmlb_tpu.engine.kv_transfer import (
    KVPages,
    KVTransferError,
    KVWireHeader,
    expected_sections,
    kv_compat_reason,
    parse_kv_payload,
    serialize_kv_pages,
)
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from tests.support import collect as _collect
from tests.support import take_tokens

# ---------------------------------------------------------------- wire format


def _header(**over) -> KVWireHeader:
    base = dict(version=1, layers=2, page_size=4, num_kv_heads=2,
                head_dim=4, kv_dtype="float32", tokens=6, num_pages=2)
    base.update(over)
    return KVWireHeader(**base)


def _sections(header: KVWireHeader) -> dict:
    out = {}
    for i, (name, (shape, dtype)) in enumerate(
            sorted(expected_sections(header).items())):
        n = int(np.prod(shape))
        out[name] = (np.arange(n, dtype=np.float64) % 97 + i) \
            .astype(dtype).reshape(shape)
    return out


def _payload(**over) -> dict:
    header = _header(**over)
    return serialize_kv_pages(header, _sections(header))


def test_int8_sections_roundtrip_bit_exact():
    """Quantized pools ship codes AND their f32 scales; both must land
    byte-identical (re-quantizing would be a silent numerics change)."""
    header = _header(kv_dtype="int8")
    sections = _sections(header)
    assert set(sections) == {"k_q", "k_s", "v_q", "v_s"}
    parsed = parse_kv_payload(serialize_kv_pages(header, sections))
    for name, arr in sections.items():
        assert parsed.sections[name].dtype == arr.dtype
        assert np.array_equal(parsed.sections[name], arr)


def test_serializer_refuses_shape_lies():
    """A malformed export must fail the exporter, never ship bytes an
    adopter would misread."""
    header = _header()
    sections = _sections(header)
    sections["k"] = sections["k"][:, :1]  # wrong num_pages axis
    with pytest.raises(KVTransferError, match="header"):
        serialize_kv_pages(header, sections)
    with pytest.raises(KVTransferError, match="sections"):
        serialize_kv_pages(_header(kv_dtype="int8"), _sections(_header()))


@pytest.mark.parametrize("mutate, match", [
    (lambda p: p.pop("data"), "data"),
    (lambda p: p.update(data="!!not-base64!!"), "base64"),
    (lambda p: p.update(data=p["data"][:16]), "base64|magic|truncated"),
    (lambda p: p.update(data=p["data"][:-8] + p["data"][:8]), "."),
    (lambda p: p.update(tokens=0), "tokens"),
    (lambda p: p.update(tokens=10_000), "tokens"),
    (lambda p: p.update(layers=True), "layers"),
    (lambda p: p.update(kv_dtype="float8"), "kv_dtype"),
    (lambda p: p.update(num_pages=0), "num_pages"),
])
def test_rejects_corrupted_payloads(mutate, match):
    payload = _payload()
    mutate(payload)
    with pytest.raises(KVTransferError, match=match):
        parse_kv_payload(payload)


def test_rejects_trailing_bytes():
    import base64
    payload = _payload()
    blob = base64.b64decode(payload["data"]) + b"\x00"
    payload["data"] = base64.b64encode(blob).decode("ascii")
    with pytest.raises(KVTransferError, match="trailing"):
        parse_kv_payload(payload)


def test_compat_reason_ordering():
    """dtype outranks page_size outranks geometry — the fallback counter's
    reason label names the FIRST incompatibility an operator must fix."""
    me = dict(layers=2, page_size=4, num_kv_heads=2, head_dim=4,
              kv_dtype="float32")
    assert kv_compat_reason(_header(), **me) is None
    assert kv_compat_reason(_header(kv_dtype="int8", page_size=8,
                                    layers=9), **me) == "dtype"
    assert kv_compat_reason(_header(page_size=8, layers=9),
                            **me) == "page_size"
    assert kv_compat_reason(_header(layers=9), **me) == "geometry"
    assert kv_compat_reason(_header(num_kv_heads=1), **me) == "geometry"
    assert kv_compat_reason(_header(head_dim=8), **me) == "geometry"


# ------------------------------------------------------------- offload tier


def _kvp(tokens=4, num_pages=1) -> KVPages:
    header = _header(tokens=tokens, num_pages=num_pages,
                     page_size=4, layers=1, num_kv_heads=1, head_dim=2)
    return KVPages(header=header, sections=_sections(header),
                   source="offload")


def test_tier_budget_lru_eviction():
    one = _kvp().nbytes
    tier = KVOffloadTier(budget_bytes=2 * one)
    assert tier.put_prefix(None, (1, 2, 3, 4), _kvp())
    assert tier.put_prefix(None, (5, 6, 7, 8), _kvp())
    assert tier.bytes_used == 2 * one
    # third entry evicts the LRU-oldest, never overruns the budget
    assert tier.put_parked("rid-1", _kvp())
    assert tier.bytes_used == 2 * one
    assert tier.evictions == 1
    assert tier.match_prefix(None, (1, 2, 3, 4), 4) is None  # evicted
    assert tier.match_prefix(None, (5, 6, 7, 8), 4) is not None


def test_tier_refuses_oversized_payload():
    tier = KVOffloadTier(budget_bytes=8)
    assert not tier.would_admit(_kvp().nbytes)
    assert not tier.put_prefix(None, (1,), _kvp())
    assert tier.bytes_used == 0
    assert KVOffloadTier(budget_bytes=0).would_admit(1) is False


def test_tier_longer_entry_matches_on_usable_head():
    """The returning-user case: the stored entry covers the FULL prompt,
    the query can only use n-1 tokens — the entry must still match on its
    head (pages are position-independent; the caller slices)."""
    tier = KVOffloadTier(budget_bytes=1 << 20)
    stored = tuple(range(48))
    tier.put_prefix(None, stored, _kvp(tokens=48, num_pages=12))
    got = tier.match_prefix(None, list(range(48)), max_len=47)
    assert got is not None
    tokens, kvp = got
    assert tokens == stored
    assert kvp.header.tokens == 48
    # consumed on hit: the caller re-lands it into HBM
    assert tier.match_prefix(None, list(range(48)), 47) is None
    assert tier.hits == 1 and tier.misses == 1


def test_tier_mismatched_head_is_a_miss():
    tier = KVOffloadTier(budget_bytes=1 << 20)
    tier.put_prefix(None, (1, 2, 3, 4), _kvp())
    assert tier.match_prefix(None, (1, 2, 9, 4), 4) is None
    assert tier.match_prefix("other-ns", (1, 2, 3, 4), 4) is None
    assert tier.misses == 2 and tier.hits == 0


def test_tier_parked_pop_and_drop():
    tier = KVOffloadTier(budget_bytes=1 << 20)
    tier.put_parked("rid-1", _kvp())
    tier.put_parked("rid-2", _kvp())
    assert tier.pop_parked("rid-1") is not None
    assert tier.pop_parked("rid-1") is None  # one-shot
    tier.drop_parked("rid-2")  # cancelled request: bytes leave the budget
    assert tier.bytes_used == 0
    assert tier.info()["parked_entries"] == 0


# ------------------------------------------------------------- engine core


def _req(prompt, max_tokens=4, temperature=0.0, seed=None, priority=1):
    return Request(prompt_ids=list(prompt),
                   sampling=SamplingParams(temperature=temperature,
                                           max_tokens=max_tokens, seed=seed,
                                           priority=priority))


def _park_roundtrip(*, offload, temperature=0.0, seed=None, quantize=None,
                    kv_ship=None):
    """Reference run, then the same request parked mid-decode by a
    priority-0 interloper (num_slots=1 forces the preemption) and resumed.
    Returns (ref_tokens, victim_tokens, prefill_dispatches_for_victim+
    interloper, kv_transfer_info)."""
    kw = dict(num_slots=1, slot_capacity=64, prefill_buckets=(16,),
              seed=0, kv_page_size=16,
              prefix_cache=False, quantize=quantize)
    if kv_ship is not None:
        kw["kv_ship"] = kv_ship
    if offload:
        kw["kv_offload_bytes"] = 1 << 28
    core = EngineCore(get_preset("debug-tiny"), **kw)
    core.start()
    try:
        prompt = [3, 5, 7, 11, 13, 17, 19, 23]
        ref, _ = _collect(core.submit(_req(prompt, max_tokens=24,
                                           temperature=temperature,
                                           seed=seed, priority=2)))
        disp0 = sum(core.prefill_dispatch_by_loop.values())
        victim = core.submit(_req(prompt, max_tokens=24,
                                  temperature=temperature, seed=seed,
                                  priority=2))
        # decoding: parked mid-generation, not queued
        toks = take_tokens(victim, 3, timeout=60)
        _collect(core.submit(_req([2] * 8, max_tokens=4, priority=0)))
        rest, _ = _collect(victim)
        toks += rest
        assert core.metrics.preemptions_total >= 1, "interloper never parked"
        disp = sum(core.prefill_dispatch_by_loop.values()) - disp0
        return ref, toks, disp, core.kv_transfer_info()
    finally:
        core.stop()


@pytest.mark.parametrize("quantize", [None, "kv"],
                         ids=["bf16-pool", "int8-pool"])
def test_park_restore_is_zero_prefill_and_token_identical(quantize):
    """THE acceptance invariant: a tier restore re-enters decode without a
    single prefill dispatch — 2 on the ledger (victim's own prefill + the
    interloper's) where the replay path needs >= 3 — and the tokens match
    the uninterrupted reference bit for bit, for plain AND int8 pools."""
    ref_r, toks_r, disp_replay, _ = _park_roundtrip(offload=False,
                                                    quantize=quantize)
    assert toks_r == ref_r
    assert disp_replay >= 3, "replay resume must re-prefill"
    ref, toks, disp, info = _park_roundtrip(offload=True, quantize=quantize)
    assert toks == ref == ref_r
    assert disp == 2, f"restore ran {disp - 2} prefill dispatches"
    assert info["offload"]["spills"] >= 1
    assert info["offload"]["hits"] >= 1
    assert info["restored_total"] >= 1
    assert info["restored_bytes_total"] > 0


def test_park_restore_seeded_stochastic_identity():
    ref, toks, _, info = _park_roundtrip(offload=True, temperature=0.9,
                                         seed=1234)
    assert toks == ref
    assert info["restored_total"] >= 1


def test_knobs_off_is_bit_identical_to_replay_only():
    """LLMLB_KV_SHIP=0 + LLMLB_KV_OFFLOAD_BYTES=0 pins today's behavior:
    same tokens, same dispatch count, nothing spilled, nothing counted."""
    ref_d, toks_d, disp_d, _ = _park_roundtrip(offload=False)
    ref, toks, disp, info = _park_roundtrip(offload=False, kv_ship=False)
    assert (ref, toks, disp) == (ref_d, toks_d, disp_d)
    assert info["ship_enabled"] is False
    assert info["ship_total"] == 0
    assert info["offload"]["enabled"] is False


def test_prefix_evicted_to_tier_rehits_without_reprefill():
    """Page pressure evicts prompt A's cached prefix D2H; A's return
    restores it H2D into the live radix cache and takes the ordinary
    zero-copy hit — one suffix chunk, not a full re-prefill."""
    rng = np.random.default_rng(11)
    cfg = get_preset("debug-tiny")
    A = list(rng.integers(1, cfg.vocab_size, size=(48,)))
    B = list(rng.integers(1, cfg.vocab_size, size=(48,)))
    core = EngineCore(cfg, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), seed=0, kv_page_size=16,
                      kv_pages=6,
                      kv_offload_bytes=1 << 28)
    core.start()
    try:
        ra, _ = _collect(core.submit(_req(A)))  # caches A's prefix
        _collect(core.submit(_req(B)))  # page pressure evicts A -> tier
        assert core.kv_transfer_info()["offload"]["spills"] >= 1
        hits0 = core.metrics.prefix_hits_total
        disp0 = sum(core.prefill_dispatch_by_loop.values())
        ra2, _ = _collect(core.submit(_req(A)))
        info = core.kv_transfer_info()
        assert ra2 == ra
        assert info["offload"]["hits"] >= 1
        assert core.metrics.prefix_hits_total == hits0 + 1
        assert info["restored_total"] >= 1
        # restored head + one suffix chunk: a single prefill dispatch
        assert sum(core.prefill_dispatch_by_loop.values()) - disp0 == 1
    finally:
        core.stop()
