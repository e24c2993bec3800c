"""The block-causal mask in the attention ops, and `models/sdar_moe.py`
against its plain reference (benchmark/reference/sdar_moe.py) at EVERY
position: prefill, extends from a page boundary and mid-page, block passes
with masks in them. Float32 on the CPU, small size. One-term controls — the
reference with one term of the published layer changed — each part from the
program by far more than rounding: the comparison would catch that term
computed wrong. What the family does not compute refused by name is the
suite's case (tests/engine/family_suite.py). Docs: docs/block-diffusion.md.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dense
from benchmark.reference import sdar_moe as ref
from llmlb_tpu.models import config_from_hf, family_for, sdar_moe
from llmlb_tpu.ops import attention as ops
from llmlb_tpu.parallel.mesh import MeshConfig, build_mesh
from tests.engine.family_suite import (  # noqa: F401 — the case it has
    Case,
    test_what_the_family_does_not_compute_is_refused_by_name,
)
from llmlb_tpu.ops.pallas_attention import (
    flash_prefill,
    paged_flash_extend,
    paged_flash_extend_quant,
)

B = 4  # the block length of every test here
MASK = 500
HF = dict(
    model_type="sdar_moe", vocab_size=512, hidden_size=64,
    intermediate_size=192, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    rope_theta=1e6, rope_scaling=None, rms_norm_eps=1e-6, num_experts=16,
    num_experts_per_tok=4, norm_topk_prob=True, mlp_only_layers=[],
    decoder_sparse_step=1, tie_word_embeddings=False, attention_bias=False,
    hidden_act="silu", max_position_embeddings=4096, sliding_window=None,
    use_sliding_window=False, max_window_layers=3,
    assumed=dict(block_length=B, mask_token_id=MASK))
PAGE = 16


@pytest.fixture(autouse=True)
def _xla_paths(monkeypatch):
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "xla")


@pytest.fixture(scope="module")
def model():
    cfg = config_from_hf(HF, jnp.float32)
    params = sdar_moe.init_params(cfg, jax.random.PRNGKey(0))
    # the head norms are ones at init, which would hide a norm left out
    for i, name in enumerate(("q_norm", "k_norm")):
        params[name] = 1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(5 + i), params[name].shape)
    return cfg, params


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(8, MASK, size=n).astype(np.int32)


# --- the ops: B = 1 is the causal op, bit for bit; B > 1 is the block mask --

def _dense_block_attention(q, k, v, q_pos, block):
    """q [T, H, D] at positions q_pos over k, v [S, K, D]: plain softmax
    attention where key j is visible to query i iff j // block <= i // block."""
    group = q.shape[1] // k.shape[1]
    k, v = (np.repeat(np.asarray(x, np.float64), group, axis=1) for x in (k, v))
    q = np.asarray(q, np.float64)
    scores = np.einsum("thd,shd->hts", q, k) / np.sqrt(q.shape[-1])
    seen = (np.arange(k.shape[0])[None, :] // block
            <= np.asarray(q_pos)[:, None] // block)
    scores = np.where(seen[None], scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", probs, v)


def _qkv(b, t, h, kv, d, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, t, h, d), jnp.float32),
            jax.random.normal(keys[1], (b, t, kv, d), jnp.float32),
            jax.random.normal(keys[2], (b, t, kv, d), jnp.float32))


def test_block_end_is_the_position_itself_at_one():
    pos = jnp.arange(11)
    assert ops._block_end(pos, 1) is pos
    np.testing.assert_array_equal(ops._block_end(pos, 4),
                                  [3, 3, 3, 3, 7, 7, 7, 7, 11, 11, 11])


@pytest.mark.parametrize("fn", ["prefill_einsum", "extend_einsum",
                                "flash_prefill", "paged_flash_extend",
                                "paged_flash_extend_quant"])
def test_a_block_of_one_traces_the_causal_program(fn):
    """B = 1 must lower to the programs the autoregressive families always
    built: the traced program (the jaxpr, Pallas kernel bodies included) is
    the same text with `block=1` as without the argument."""
    b, t, h, kv, d, ps, ppn = 2, 16, 4, 2, 16, 8, 3
    q, k, v = _qkv(b, t, h, kv, d)
    lens = jnp.asarray([t, t - 5], jnp.int32)
    pool = jax.random.normal(jax.random.PRNGKey(3),
                             (2, b * ppn + 1, ps, kv, d), jnp.float32)
    tables = jnp.arange(1, b * ppn + 1, dtype=jnp.int32).reshape(b, ppn)
    start = jnp.asarray([8, 3], jnp.int32)
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    qpool = pool.astype(jnp.int8)
    scales = jnp.ones((b * ppn + 1, ps, kv), jnp.float32)
    calls = {
        "prefill_einsum": lambda **kw: ops._prefill_einsum(q, k, v, lens, **kw),
        "extend_einsum": lambda **kw: ops.gqa_attention_extend(
            q, pool[0, 1:3].reshape(1, 2 * ps, kv, d).repeat(b, 0),
            pool[1, 1:3].reshape(1, 2 * ps, kv, d).repeat(b, 0),
            positions % (2 * ps), **kw),
        "flash_prefill": lambda **kw: flash_prefill(
            q, k, v, lens, block_q=8, block_k=8, interpret=True, **kw),
        "paged_flash_extend": lambda **kw: paged_flash_extend(
            q, pool, pool, 1, tables, start, lens, interpret=True, **kw),
        "paged_flash_extend_quant": lambda **kw: paged_flash_extend_quant(
            q, qpool, scales, qpool, scales, 1, tables, start, lens,
            interpret=True, **kw),
    }
    call = calls[fn]
    as_it_was = str(jax.make_jaxpr(lambda: call())())
    assert str(jax.make_jaxpr(lambda: call(block=1))()) == as_it_was
    assert str(jax.make_jaxpr(lambda: call(block=B))()) != as_it_was
    np.testing.assert_array_equal(call(block=1), call())


@pytest.mark.parametrize("pallas", [False, True], ids=["einsum", "pallas"])
def test_prefill_under_the_block_mask(pallas):
    b, t, h, kv, d = 2, 32, 8, 2, 16
    q, k, v = _qkv(b, t, h, kv, d, seed=2)
    lens = jnp.asarray([32, 20], jnp.int32)
    got = (flash_prefill(q, k, v, lens, block_q=8, block_k=8, interpret=True,
                         block=B) if pallas
           else ops.gqa_attention_prefill(q, k, v, lens, block=B))
    for r, n in enumerate(np.asarray(lens)):
        want = _dense_block_attention(q[r, :n], k[r, :n], v[r, :n],
                                      np.arange(n), B)
        np.testing.assert_allclose(got[r, :n], want, rtol=2e-5, atol=2e-5)
    causal = ops.gqa_attention_prefill(q, k, v, lens)
    assert np.abs(np.asarray(got[0]) - np.asarray(causal[0])).max() > 1e-2


@pytest.mark.parametrize("route", ["xla", "pallas", "pallas-int8"])
def test_extend_under_the_block_mask(route):
    """A chunk of whole blocks behind a committed prefix, from a page
    boundary (row 0) and mid-page (row 1); the KV-block skip of the kernels
    moves with the mask (a chunk's last block sees keys past its queries'
    positions... inside the block only)."""
    b, t, h, kv, d, ps, ppn, layer = 2, 8, 8, 2, 16, 8, 4, 1
    q, _, _ = _qkv(b, t, h, kv, d, seed=3)
    pool_k, pool_v = (jax.random.normal(
        jax.random.PRNGKey(s), (2, b * ppn + 1, ps, kv, d), jnp.float32)
        for s in (7, 8))
    tables = jnp.arange(1, b * ppn + 1, dtype=jnp.int32).reshape(b, ppn)
    start = jnp.asarray([8, 12], jnp.int32)
    lens = jnp.asarray([t, t], jnp.int32)
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    tol = dict(rtol=2e-5, atol=2e-5)
    if route == "xla":
        got = ops.paged_attention_extend(q, pool_k, pool_v, layer, tables,
                                         positions, lens, block=B)
    elif route == "pallas":
        got = paged_flash_extend(q, pool_k, pool_v, layer, tables, start,
                                 lens, block_q=4, interpret=True, block=B)
    else:
        from llmlb_tpu.quant import quantize_kv

        kq, ks = quantize_kv(pool_k)
        vq, vs = quantize_kv(pool_v)
        got = paged_flash_extend_quant(
            q, kq, ks[layer], vq, vs[layer], layer, tables, start, lens,
            block_q=4, interpret=True, block=B)
        pool_k = kq.astype(jnp.float32) * ks[..., None]
        pool_v = vq.astype(jnp.float32) * vs[..., None]
        tol = dict(rtol=1e-4, atol=1e-4)
    for r in range(b):
        k_row = np.asarray(pool_k[layer])[np.asarray(tables[r])].reshape(-1, kv, d)
        v_row = np.asarray(pool_v[layer])[np.asarray(tables[r])].reshape(-1, kv, d)
        want = _dense_block_attention(q[r], k_row, v_row, positions[r], B)
        np.testing.assert_allclose(got[r], want, **tol)


# --- the family against the reference, at every position --------------------

def _pool(cfg, rows, pages_per_row):
    ck, cv = sdar_moe.init_kv_pages(cfg, rows * pages_per_row + 1, PAGE)
    tables = jnp.asarray(1 + np.arange(rows * pages_per_row, dtype=np.int32)
                         .reshape(rows, pages_per_row))
    return ck, cv, tables


def _program_logits(cfg, params, ids, prefill, chunks):
    """Every position's logits of `ids` through the paged functions: a
    prefill of `prefill` tokens (compared at its last position only: that
    is what it returns), then all-position chunks (verify_step_paged)."""
    ck, cv, tables = _pool(cfg, 1, 8)
    pad = np.zeros((1, 32), np.int32)
    pad[0, :prefill] = ids[:prefill]
    out = {}
    logits, ck, cv, *_ = sdar_moe.prefill_into_pages(
        params, cfg, jnp.asarray(pad), jnp.asarray([prefill], np.int32),
        tables, ck, cv, None)
    out[prefill - 1] = np.asarray(logits[0])
    pos = prefill
    for n in chunks:
        logits, ck, cv, *_ = sdar_moe.verify_step_paged(
            params, cfg, jnp.asarray(ids[None, pos:pos + n]),
            jnp.asarray([n], np.int32), jnp.asarray([pos], np.int32), tables,
            ck, cv, None)
        for i in range(n):
            out[pos + i] = np.asarray(logits[0, i])
        pos += n
    return out


def test_prefill_and_extends_agree_with_the_reference_at_every_position(model):
    cfg, params = model
    ids = _ids(52)
    want = np.asarray(ref.forward(params, HF, ids)[0])
    # prefill 16 (one page), extends from the page boundary (16), then from
    # mid-page (24, 28, 44)
    got = _program_logits(cfg, params, ids, 16, [8, 4, 16, 8])
    assert sorted(got) == [15, *range(16, 52)]
    for p, row in got.items():
        np.testing.assert_allclose(row, want[p], rtol=0, atol=1e-5,
                                   err_msg=f"position {p}")
    # the last-position path (prefill_extend_pages) at a chunk of two blocks
    ck, cv, tables = _pool(cfg, 1, 8)
    _, ck, cv, *_ = sdar_moe.prefill_into_pages(
        params, cfg, jnp.asarray(np.pad(ids[:20], (0, 12))[None]),
        jnp.asarray([20], np.int32), tables, ck, cv, None)
    logits, *_ = sdar_moe.prefill_extend_pages(
        params, cfg, jnp.asarray(ids[None, 20:28]), jnp.asarray([8], np.int32),
        jnp.asarray([20], np.int32), tables, ck, cv, None)
    np.testing.assert_allclose(logits[0], want[27], rtol=0, atol=1e-5)


@pytest.mark.parametrize("masked", [(1,), (0, 3), (0, 1, 2), (0, 1, 2, 3), ()])
def test_a_block_pass_with_masks_agrees_at_every_position(model, masked):
    """The engine's block pass: B ids behind the committed cache, some of
    them the mask token; then the committing pass over the true ids leaves
    the cache as a prefill of them would."""
    cfg, params = model
    ids = _ids(28, seed=3)
    ck, cv, tables = _pool(cfg, 2, 4)
    rows = np.stack([ids[:20], _ids(20, seed=4)])
    _, ck, cv, *_ = sdar_moe.prefill_into_pages(
        params, cfg, jnp.asarray(np.pad(rows, ((0, 0), (0, 12)))),
        jnp.asarray([20, 20], np.int32), tables, ck, cv, None)
    block = ids[20:24].copy()
    block[list(masked)] = MASK
    # row 1 is not decoding: chunk length 0, its logits are discarded
    both = np.stack([block, block])
    lens, start = jnp.asarray([B, 0], np.int32), jnp.asarray([20, 63], np.int32)
    logits, ck, cv, *_ = sdar_moe.verify_step_paged(
        params, cfg, jnp.asarray(both), lens, start, tables, ck, cv, None)
    want = np.asarray(ref.forward(
        params, HF, np.concatenate([ids[:20], block]))[0])[20:]
    np.testing.assert_allclose(logits[0], want, rtol=0, atol=1e-5)
    # commit, then the next block sees the committed one
    both = np.stack([ids[20:24]] * 2)
    _, ck, cv, *_ = sdar_moe.verify_step_paged(
        params, cfg, jnp.asarray(both), lens, start, tables, ck, cv, None)
    logits, ck, cv, *_ = sdar_moe.verify_step_paged(
        params, cfg, jnp.asarray(np.stack([ids[24:28]] * 2)), lens,
        jnp.asarray([24, 63], np.int32), tables, ck, cv, None)
    np.testing.assert_allclose(
        logits[0], np.asarray(ref.forward(params, HF, ids)[0])[24:],
        rtol=0, atol=1e-5)


@pytest.mark.parametrize("route,idle", [("xla", 1), ("pallas", 2)])
def test_a_pass_two_blocks_wide_agrees_with_two_passes_of_one(
        model, monkeypatch, route, idle):
    """The scheduler's pass (programs._build_block_many): T = 2B, and in
    one batch a row that commits and goes on ([its complete block | B
    masks], chunk length 2B, logits wanted from B), a row that unmasks
    ([its open block | padding], chunk length B, logits wanted from 0) and
    rows that are not decoding (chunk length 0). At every position wanted
    it gives what two calls at T = B give — the commit, then the new
    block's first pass — the committed block's K and V are the same in the
    pool, and a padding position reaches no expert. (Another batch a route:
    the route is read while tracing.)"""
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", route)
    cfg, params = model
    n = 2 + idle
    ids, other = _ids(24, seed=3), _ids(24, seed=4)
    masks = np.full(B, MASK, np.int32)
    opened = other[20:24].copy()
    opened[[0, 3]] = MASK
    junk = _ids(B, seed=9)

    def lens(*live):
        return jnp.asarray([*live] + [0] * idle, np.int32)

    def start(*live):
        return jnp.asarray([*live] + [63] * idle, np.int32)

    def prefilled():
        ck, cv, tables = _pool(cfg, n, 4)
        rows = np.stack([ids[:20], other[:20]] + [other[:20]] * idle)
        _, ck, cv, *_ = sdar_moe.prefill_into_pages(
            params, cfg, jnp.asarray(np.pad(rows, ((0, 0), (0, 12)))),
            jnp.full((n,), 20, np.int32), tables, ck, cv, None)
        return ck, cv, tables

    def stack(*rows):
        return jnp.asarray(np.stack([*rows] + [rows[-1]] * idle))

    ck, cv, tables = prefilled()
    first, ck, cv, *_ = sdar_moe.verify_step_paged(
        params, cfg, stack(ids[20:24], opened), lens(B, B), start(20, 20),
        tables, ck, cv, None)
    second, ck, cv, *_ = sdar_moe.verify_step_paged(
        params, cfg, stack(masks, junk), lens(B, 0), start(24, 63), tables,
        ck, cv, None)
    ck2, cv2, tables = prefilled()
    wide, ck2, cv2, counters = sdar_moe.verify_step_paged(
        params, cfg, stack(np.concatenate([ids[20:24], masks]),
                           np.concatenate([opened, junk])),
        lens(2 * B, B), start(20, 20), tables, ck2, cv2, None,
        logits_from=jnp.asarray([B, 0] + [0] * idle, np.int32), logits_len=B)
    assert wide.shape == (n, B, cfg.vocab_size)
    np.testing.assert_allclose(wide[0], second[0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(wide[1], first[1], rtol=0, atol=1e-5)
    want = np.asarray(ref.forward(
        params, HF, np.concatenate([ids[:24], masks]))[0])[24:]
    np.testing.assert_allclose(wide[0], want, rtol=0, atol=1e-5)
    # the committing row's cells: the complete block and the open one
    for a, b in ((ck, ck2), (cv, cv2)):
        a, b = (np.asarray(pool)[:, np.asarray(tables[0])].reshape(
            cfg.num_layers, -1, *pool.shape[3:])[:, 20:28] for pool in (a, b))
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
    assert int(counters["expert_assignments"]) == (
        cfg.num_layers * (2 * B + B) * cfg.experts_per_token)


def test_the_routing_report_and_the_counters(model):
    cfg, params = model
    ids = _ids(16, seed=6)
    ck, cv, tables = _pool(cfg, 1, 4)
    out = sdar_moe.prefill_into_pages(
        params, cfg, jnp.asarray(ids[None]), jnp.asarray([16], np.int32),
        tables, ck, cv, None, routing=True)
    chosen, logits, kept = out[3]
    assert chosen.shape == (3, 1, 16, 4) and logits.shape == (3, 1, 16, 16)
    assert bool(kept.all())
    want_router = np.asarray(ref.forward(params, HF, ids)[1])
    np.testing.assert_allclose(logits[:, 0], want_router, atol=1e-5)
    ck, cv, tables = _pool(cfg, 1, 4)
    counters = sdar_moe.prefill_into_pages(
        params, cfg, jnp.asarray(ids[None]), jnp.asarray([16], np.int32),
        tables, ck, cv, None)[3]
    assert int(counters["expert_assignments"]) == 3 * 16 * 4
    assert 0 < int(counters["experts_touched"]) <= 3 * 16
    assert set(sdar_moe.step_counters(cfg)) == set(counters)


# --- one-term controls: each must fail the comparison above -----------------

def _rope_interleaved(x, theta):
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention_variant(kind):
    def attention(h, wq, wk, wv, wo, q_norm, k_norm, *, heads, kv_heads,
                  head_dim, theta, eps, block):
        t = h.shape[0]
        q = (h @ wq).reshape(t, heads, head_dim)
        k = (h @ wk).reshape(t, kv_heads, head_dim)
        v = (h @ wv).reshape(t, kv_heads, head_dim)
        rope = _rope_interleaved if kind == "interleaved_rope" else dense.rope
        if kind == "no_qk_norm":
            q, k = rope(q, theta), rope(k, theta)
        elif kind == "norm_after_rope":
            q = dense.rms_norm(rope(q, theta), q_norm, eps)
            k = dense.rms_norm(rope(k, theta), k_norm, eps)
        else:
            q = rope(dense.rms_norm(q, q_norm, eps), theta)
            k = rope(dense.rms_norm(k, k_norm, eps), theta)
        k, v = (jnp.repeat(x, heads // kv_heads, axis=1) for x in (k, v))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
            jnp.float32(head_dim))
        pos = jnp.arange(t)
        seen = {"causal_in_block": pos[None, :] <= pos[:, None],
                # earlier blocks hidden but for their last position
                "hides_earlier_tails": (
                    (pos[None, :] // block == pos[:, None] // block)
                    | (pos[None, :] % block == block - 1)
                    & (pos[None, :] < pos[:, None])),
                }.get(kind, ref.block_mask(t, block))
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(t, heads * head_dim) @ wo

    return attention


def _mixture_not_renormalised(h, l, router, we_gate, we_up, we_down, chosen,
                              *, top_k):
    logits = h @ router
    probs = jax.nn.softmax(logits, axis=-1)
    weights, chosen = jax.lax.top_k(probs, top_k)  # their sum is under 1
    out = jnp.zeros_like(h)
    for e in range(router.shape[-1]):
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        out = out + w_e[:, None] * dense.swiglu(
            h, we_gate[l, e], we_up[l, e], we_down[l, e])
    return out, logits


CONTROLS = {
    "no_qk_norm": ("attention", _attention_variant("no_qk_norm")),
    "norm_after_rope": ("attention", _attention_variant("norm_after_rope")),
    "causal_in_block": ("attention", _attention_variant("causal_in_block")),
    "hides_earlier_tails": ("attention",
                            _attention_variant("hides_earlier_tails")),
    "interleaved_rope": ("attention", _attention_variant("interleaved_rope")),
    "not_renormalised": ("mixture", _mixture_not_renormalised),
    # the variant builder with no term changed: it IS the reference
    "sound": ("attention", _attention_variant("sound")),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_one_term_controls_fail_and_the_sound_variant_passes(
        model, control, monkeypatch):
    cfg, params = model
    ids = _ids(36, seed=9)
    got = _program_logits(cfg, params, ids, 16, [8, 12])
    name, fn = CONTROLS[control]
    monkeypatch.setattr(ref, name, fn)
    ref.layer.clear_cache()  # traced with the module's functions as they are
    try:
        want = np.asarray(ref.forward(params, HF, ids)[0])
    finally:
        ref.layer.clear_cache()
    err = max(np.abs(row - want[p]).max() for p, row in got.items())
    if control == "sound":
        assert err < 1e-5
    else:
        assert err > 1e-3, f"{control} reads {err}: the comparison is blind to it"


# --- the configuration class ------------------------------------------------

def test_the_configuration_reads_every_key_and_the_experts_own_width(model):
    cfg, params = model
    assert family_for(cfg) is sdar_moe and sdar_moe.block_length(cfg) == B
    assert (cfg.num_experts, cfg.experts_per_token,
            cfg.moe_intermediate_size, cfg.intermediate_size) == (16, 4, 32, 192)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (8, 2, 16)
    assert cfg.rope_theta == 1e6 and cfg.rms_eps == 1e-6
    assert (cfg.denoising_steps, cfg.remasking_strategy,
            cfg.confidence_threshold, cfg.mask_token_id) == (
        4, "low_confidence_dynamic", 0.9, MASK)
    # `intermediate_size` is the width of dense layers this model has none of
    assert params["we_gate"].shape == (3, 16, 64, 32)
    assert params["we_down"].shape == (3, 16, 32, 64)
    assert params["q_norm"].shape == params["k_norm"].shape == (3, 16)
    mesh = build_mesh(MeshConfig(dp=1, ep=1, tp=1), devices=jax.devices()[:1])
    assert set(sdar_moe.param_shardings(cfg, mesh)) >= set(params)


CASE = Case(
    family=sdar_moe, preset="debug-sdar-tiny", hf=HF,
    refused=tuple(({key: value}, key) for key, value in (
        ("mlp_only_layers", [1]), ("decoder_sparse_step", 2),
        ("use_sliding_window", True), ("tie_word_embeddings", True),
        ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
        ("attention_bias", True), ("norm_topk_prob", False),
        ("hidden_act", "gelu"))))


@pytest.mark.parametrize("assumed,message", [
    (dict(denoising_steps=3), "denoising_steps"),
    (dict(remasking_strategy="random"), "remasking_strategy"),
    (dict(confidence_threshold=1.5), "confidence_threshold"),
    (dict(mask_token_id=512), "mask_token_id")])
def test_generation_defaults_are_checked(assumed, message):
    with pytest.raises(ValueError, match=message):
        config_from_hf({**HF, "assumed": {**HF["assumed"], **assumed}},
                       jnp.float32)


def test_an_unknown_mixture_with_its_own_expert_width_is_refused():
    """The repair of PR 34 in models/__init__.py: a config of an unknown
    `model_type` with experts was read as Mixtral with `intermediate_size`
    as the expert width, without a word — the parent's path on this very
    model (67 GB of weights). Refused where `moe_intermediate_size` says
    otherwise; read as before where the two agree or the key is absent."""
    hf = {k: v for k, v in HF.items() if k != "assumed"}
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        config_from_hf({**hf, "model_type": "some_new_moe"}, jnp.float32)
    for same in ({"moe_intermediate_size": 192}, {}):
        read = {k: v for k, v in hf.items() if k != "moe_intermediate_size"}
        cfg = config_from_hf({**read, **same, "model_type": "some_new_moe"},
                             jnp.float32)
        assert type(cfg).__name__ == "MixtralConfig"
        assert cfg.intermediate_size == 192


def test_a_block_of_one_is_the_family_contract(model):
    """`decode_step_paged` stays for the family contract: with a block
    length of 1 the family is an autoregressive QK-normed mixture, and a
    decode step agrees with the causal reference."""
    cfg, params = model
    cfg1 = dataclasses.replace(cfg, block_length=1, denoising_steps=1)
    hf1 = {**HF, "assumed": {**HF["assumed"], "block_length": 1}}
    ids = _ids(21, seed=11)
    want = np.asarray(ref.forward(params, hf1, ids)[0])
    ck, cv, tables = _pool(cfg1, 1, 4)
    _, ck, cv, *_ = sdar_moe.prefill_into_pages(
        params, cfg1, jnp.asarray(np.pad(ids[:20], (0, 12))[None]),
        jnp.asarray([20], np.int32), tables, ck, cv, None)
    logits, *_ = sdar_moe.decode_step_paged(
        params, cfg1, jnp.asarray(ids[20:21]), jnp.asarray([20], np.int32),
        ck, cv, tables, None, window=64)
    np.testing.assert_allclose(logits[0], want[20], rtol=0, atol=1e-5)
