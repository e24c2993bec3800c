"""The two plain float32 references against models/llama.py and
models/mixtral.py at a tiny width: prefill through the block table, a
chunked extend, then decode steps through the paged cache, compared at the
logits — the comparison the launcher makes at full width on the chip
(benchmark/correctness.py)."""

import json
import os

import jax
import pytest

from benchmark import correctness
from benchmark.launcher import build_cfg
from llmlb_tpu.models import family_for

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name, **changes):
    with open(os.path.join(HERE, "rehearsal", "configs", name + ".json")) as f:
        config = json.load(f)
    config.update(changes)
    return config


def run(config, params=None, reference_params=None, **spec):
    cfg = build_cfg(config)
    family = family_for(cfg)
    if params is None:
        params = family.init_params(cfg, jax.random.PRNGKey(3))
    if reference_params is not None:  # the reference keeps the true weights
        forward = correctness.reference_forward(config)
        correctness_forward = correctness.reference_forward
        correctness.reference_forward = lambda hf: (
            lambda _p, h, ids: forward(reference_params, h, ids))
    try:
        return correctness.check(
            family, cfg, params, config,
            {**config["correctness"], "tolerance": 1.0, **spec}, 7,
            config["engine"]["kv_page_size"]), params, family
    finally:
        if reference_params is not None:
            correctness.reference_forward = correctness_forward


@pytest.mark.parametrize("name", ["debug-tiny", "debug-moe-tiny"])
def test_float32_engine_agrees_with_the_reference_to_rounding(name):
    out, _, _ = run(load(name))
    assert out["positions_compared"] == 1 + 1 + 4  # prefill, extend, 4 decodes
    assert out["max_rel_rms_err"] < 1e-4
    assert out["decode_rel_rms_err"] < 1e-4


def test_moe_reference_is_exact_top2_and_the_capacity_path_departs():
    # 64 tokens > 4 x 4 experts: the program's capacity dispatch may drop
    # tokens, the reference never does (PERF.md, Open questions)
    config = load("debug-moe-tiny")
    exact, _, _ = run(config, prefill_tokens=16)
    assert exact["prefill_rel_rms_err"] < 1e-4
    capacity, _, _ = run(config, prefill_tokens=32, extend_chunks=0)
    assert capacity["prefill_rel_rms_err"] >= exact["prefill_rel_rms_err"]


def test_the_tolerance_separates_bf16_serving_from_int8_weights():
    """The chip's tolerance is about twice what bf16 activations cost; at
    that, int8 weights — a lower precision than the configuration states —
    must fail. Shown here at 8 layers of width 512."""
    from llmlb_tpu.quant import quantize_params

    config = load("debug-tiny", torch_dtype="bfloat16", hidden_size=512,
                  intermediate_size=1536, num_hidden_layers=8)
    bf16, params, _ = run(config)
    int8, _, _ = run(config, params=quantize_params(params),
                     reference_params=params)
    tolerance = 2 * bf16["max_rel_rms_err"]
    assert bf16["max_rel_rms_err"] < 0.03
    assert int8["max_rel_rms_err"] > tolerance


@pytest.mark.parametrize("key,value", [("rope_theta", 500.0),
                                       ("rms_norm_eps", 0.5),
                                       ("num_hidden_layers", 1)])
def test_a_changed_or_skipped_term_fails(key, value):
    """The reference reads its own dimensions from the configuration: give
    it another rope base, norm epsilon or one layer fewer and the logits
    part by far more than any tolerance."""
    config = load("debug-tiny")
    cfg = build_cfg(config)
    family = family_for(cfg)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    wrong = {**config, key: value}
    out = correctness.check(family, cfg, params, wrong,
                            {**config["correctness"], "tolerance": 0.05}, 7, 16)
    assert not out["ok"] and out["max_rel_rms_err"] > 0.05


def test_rel_rms_err():
    import numpy as np

    want = np.array([[3.0, 4.0]])
    assert correctness.rel_rms_err(want, want) == 0.0
    assert correctness.rel_rms_err(want * 1.1, want) == pytest.approx(0.1)
