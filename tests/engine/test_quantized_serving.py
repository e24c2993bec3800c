"""Int8 quantized serving (llmlb_tpu/quant, docs/quantization.md).

Covers the acceptance bars at the engine level on the CPU backend:
- `quantize="off"` is provably zero-cost: greedy AND seeded streams are
  bit-identical to an engine constructed without the knob, both layouts.
- int8-KV engines serve end to end (prefill, decode, chunked prefill,
  prefix-cache zero-copy sharing) and report halved bytes/page.
- spec-decode on int8 pages: rejected-suffix rollback releases pages
  exactly once (PagePool double-free guard armed) and the pool drains
  clean at a tiny page size.
- weight quantization: params carry int8+scale pairs, output stays
  plausible (greedy decode completes), and the streaming checkpoint
  loader produces the same layout the core's own pass does.
"""

import queue

import numpy as np
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
from tests.support import assert_hit_is_zero_copy
from tests.support import collect as _collect


def _req(prompt, max_tokens=8, temperature=0.0, seed=None, spec=None):
    return Request(prompt_ids=list(prompt),
                   sampling=SamplingParams(temperature=temperature,
                                           max_tokens=max_tokens,
                                           seed=seed, speculative=spec))


def _core(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("slot_capacity", 64)
    kw.setdefault("prefill_buckets", (16, 32))
    kw.setdefault("seed", 0)
    kw.setdefault("kv_page_size", 16)
    return EngineCore(get_preset("debug-tiny"), **kw)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(7)
    cfg = get_preset("debug-tiny")
    return [list(rng.integers(1, cfg.vocab_size, size=(n,)))
            for n in (24, 12, 40)]


# ------------------------------------------------------- off == bit-identical


@pytest.mark.parametrize("kv_page", [16, 4], ids=["paged", "paged-page4"])
def test_quantize_off_bit_identical(prompts, kv_page):
    """The zero-cost-when-disabled acceptance bar: greedy and seeded
    stochastic streams from a quantize="off" engine match an engine built
    without the knob token for token (at a page the size of the smallest
    prefill bucket, and at one a quarter of it)."""
    streams = {}
    for label, quantize in (("default", None), ("off", "off")):
        core = _core(kv_page_size=kv_page, quantize=quantize)
        core.start()
        try:
            reqs = [
                _req(prompts[0], max_tokens=10),  # greedy
                _req(prompts[1], max_tokens=10, temperature=0.9, seed=42),
                _req(prompts[2], max_tokens=10, temperature=0.7, seed=7),
            ]
            for r in reqs:
                core.submit(r)
            streams[label] = [_collect(r)[0] for r in reqs]
        finally:
            core.stop()
    assert streams["default"] == streams["off"]


# -------------------------------------------------------------- int8 KV pages


def test_int8_kv_serves_and_reports_halved_bytes(prompts):
    # prefix_cache off so the drain check below sees a fully-free pool
    # (donor pins are covered by test_int8_kv_prefix_hit_stays_zero_copy)
    core = _core(quantize="kv", prefix_cache=False)
    core.start()
    try:
        reqs = [_req(p, max_tokens=6) for p in prompts]
        for r in reqs:
            core.submit(r)
        for r in reqs:
            toks, finish = _collect(r)
            assert finish in ("stop", "length")
            assert len(toks) >= 1
        info = core.kv_cache_info()
        assert info["kv_dtype"] == "int8"
        bf16 = _core(quantize="off")
        try:
            base = bf16.kv_cache_info()
        finally:
            bf16.stop()
        # (D·1 + 4) / (D·itemsize): strictly under 60% of the bf16 page
        assert info["bytes_per_page"] < 0.6 * base["bytes_per_page"]
        assert info["hbm_bytes"] < 0.6 * base["hbm_bytes"]
        # pool fully reclaimed (scales carry no separate page bookkeeping)
        assert core.page_pool.available() == core.page_pool.total
    finally:
        core.stop()


def test_int8_kv_prefix_hit_stays_zero_copy(prompts):
    """Zero-copy sharing is page-id bookkeeping; the scale arrays ride the
    same ids, so a hit must still build and dispatch no program of its own
    (40-token prompt, 32 shared: the suffix is 8 tokens)."""
    core = _core(quantize="kv")
    core.start()
    try:
        _collect(core.submit(_req(prompts[2])))
        with assert_hit_is_zero_copy(core, suffix_tokens=8):
            _collect(core.submit(_req(prompts[2])))
        assert core.metrics.prefix_hits_total == 1
    finally:
        core.stop()


def test_int8_kv_greedy_parity_with_bf16(prompts):
    """Token-level divergence is allowed but must be mild on a tiny model
    with short generations: the first few greedy tokens track bf16."""
    outs = {}
    for label, quantize in (("bf16", "off"), ("int8", "kv")):
        core = _core(quantize=quantize)
        core.start()
        try:
            r = _req(prompts[0], max_tokens=6)
            core.submit(r)
            outs[label] = _collect(r)[0]
        finally:
            core.stop()
    assert len(outs["int8"]) == len(outs["bf16"])
    # prefix attention reads fresh bf16 K/V, so the FIRST token (sampled
    # from prefill logits) is exact; later tokens may drift
    assert outs["int8"][0] == outs["bf16"][0]


def test_spec_decode_on_int8_pages_rolls_back_cleanly():
    """Speculative decoding over int8 pages: rejected-suffix rollback
    releases over-allocated pages exactly once (the PagePool double-free
    guard would raise otherwise) and the pool drains clean at page_size 4.
    Prompts with repeated n-grams guarantee the drafter proposes."""
    cfg = get_preset("debug-tiny")
    core = EngineCore(cfg, num_slots=4, slot_capacity=64,
                      prefill_buckets=(16, 32), seed=0,
                      kv_page_size=4, quantize="kv", spec_decode=True,
                      spec_max_draft=3, prefix_cache=False)
    core.start()
    try:
        pattern = [9, 8, 7, 6] * 6  # strong n-gram structure
        reqs = [_req(pattern, max_tokens=16,
                     spec={"enabled": True, "max_draft_tokens": 3})
                for _ in range(4)]
        for r in reqs:
            core.submit(r)
        for r in reqs:
            toks, finish = _collect(r)
            assert finish in ("stop", "length")
            assert len(toks) >= 1
        assert core.metrics.spec_verify_steps_total >= 1
        assert core.page_pool.available() == core.page_pool.total, (
            "int8 spec-decode rollback leaked or double-freed pages"
        )
    finally:
        core.stop()


def test_int8_kv_seeded_stream_is_reproducible(prompts):
    """Per-request seeds stay deterministic on quantized pages (two runs,
    same engine config, identical streams)."""
    runs = []
    for _ in range(2):
        core = _core(quantize="kv")
        core.start()
        try:
            r = _req(prompts[1], max_tokens=8, temperature=0.8, seed=11)
            core.submit(r)
            runs.append(_collect(r)[0])
        finally:
            core.stop()
    assert runs[0] == runs[1]


# ------------------------------------------------------------- int8 weights


def test_int8_weights_layout_and_serving(prompts):
    core = _core(quantize="weights")
    core.start()
    try:
        assert core.params["wq"].dtype == np.int8
        assert "wq_scale" in core.params
        assert core.quant_info()["param_bytes"] < core.quant_info()[
            "param_bytes_bf16"
        ]
        r = _req(prompts[0], max_tokens=6)
        core.submit(r)
        toks, finish = _collect(r)
        assert finish in ("stop", "length") and len(toks) >= 1
    finally:
        core.stop()


def test_quantize_all_through_service_health():
    from llmlb_tpu.engine.service import Engine

    eng = Engine.from_preset(
        "debug-tiny", num_slots=2, slot_capacity=64, prefill_buckets=(16,),
        kv_page_size=16, quantize="all",
    )
    try:
        health = eng.health()
        assert health["quant"]["mode"] == "all"
        assert health["kv_cache"]["kv_dtype"] == "int8"
        stats = eng.core.stats()
        text = eng.core.metrics.render(
            queue_depth=stats.queued, active_slots=stats.active_slots,
            num_slots=stats.num_slots, kv_cache=eng.core.kv_cache_info(),
            quant=eng.core.quant_info(),
        )
        assert 'llmlb_engine_quant_mode{mode="all"} 1' in text
        assert "llmlb_engine_kv_bytes_per_page" in text
        assert "llmlb_engine_param_bytes" in text
    finally:
        eng.shutdown()


def test_streaming_loader_matches_core_quantization(tmp_path):
    """engine/weights.py quantize-while-streaming must produce the same
    int8 layout EngineCore's own pass produces from the same checkpoint."""
    import jax
    from safetensors.numpy import save_file

    from llmlb_tpu.engine.weights import load_checkpoint
    from llmlb_tpu.models import llama
    from llmlb_tpu.quant import quantize_params

    cfg = get_preset("debug-tiny")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    state = {}
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}"
        state[f"{pre}.self_attn.q_proj.weight"] = np.asarray(
            params["wq"][i]).T
        state[f"{pre}.self_attn.k_proj.weight"] = np.asarray(
            params["wk"][i]).T
        state[f"{pre}.self_attn.v_proj.weight"] = np.asarray(
            params["wv"][i]).T
        state[f"{pre}.self_attn.o_proj.weight"] = np.asarray(
            params["wo"][i]).T
        state[f"{pre}.mlp.gate_proj.weight"] = np.asarray(params["wg"][i]).T
        state[f"{pre}.mlp.up_proj.weight"] = np.asarray(params["wu"][i]).T
        state[f"{pre}.mlp.down_proj.weight"] = np.asarray(params["wd"][i]).T
        state[f"{pre}.input_layernorm.weight"] = np.asarray(
            params["ln_attn"][i])
        state[f"{pre}.post_attention_layernorm.weight"] = np.asarray(
            params["ln_mlp"][i])
    state["model.embed_tokens.weight"] = np.asarray(params["embed"])
    state["model.norm.weight"] = np.asarray(params["ln_final"])
    state["lm_head.weight"] = np.asarray(params["lm_head"]).T
    save_file(state, str(tmp_path / "model.safetensors"))

    loaded = load_checkpoint(str(tmp_path), cfg, quantize_weights=True)
    direct = quantize_params({k: np.asarray(v) for k, v in params.items()})
    assert set(loaded) == set(direct)
    for name in ("wq", "wq_scale", "wd", "wd_scale"):
        np.testing.assert_array_equal(np.asarray(loaded[name]),
                                      np.asarray(direct[name]))
