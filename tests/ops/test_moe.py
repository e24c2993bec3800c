"""MoE dispatch/combine + Mixtral model: correctness vs a dense per-token
reference, capacity-drop semantics, and expert-parallel sharding equivalence
on the virtual 8-device CPU mesh (SURVEY.md §4 strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops.moe import default_capacity, moe_dispatch_combine, top_k_routing
from llmlb_tpu.parallel.mesh import MeshConfig, build_mesh
from tests.support import identity_kv_pages


def _dense_reference(x, logits, wg, wu, wd, k):
    """Per-token loop: exact top-k MoE with no capacity limit."""
    s, m = x.shape
    weights, idx = top_k_routing(jnp.asarray(logits, jnp.float32), k)
    weights, idx = np.asarray(weights), np.asarray(idx)
    x, wg, wu, wd = map(np.asarray, (x, wg, wu, wd))
    out = np.zeros_like(x)
    for t in range(s):
        for j in range(k):
            e = idx[t, j]
            h = x[t] @ wg[e]
            h = (h / (1 + np.exp(-h))) * (x[t] @ wu[e])
            out[t] += weights[t, j] * (h @ wd[e])
    return out


def _rand_moe(key, s, m, f, e):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (s, m), jnp.float32)
    logits = jax.random.normal(ks[1], (s, e), jnp.float32)
    wg = jax.random.normal(ks[2], (e, m, f), jnp.float32) * m**-0.5
    wu = jax.random.normal(ks[3], (e, m, f), jnp.float32) * m**-0.5
    wd = jax.random.normal(ks[4], (e, f, m), jnp.float32) * f**-0.5
    return x, logits, wg, wu, wd


@pytest.mark.parametrize("k", [1, 2])
def test_moe_matches_dense_reference(k):
    s, m, f, e = 32, 16, 24, 4
    x, logits, wg, wu, wd = _rand_moe(jax.random.PRNGKey(0), s, m, f, e)
    # capacity = s: no token can overflow even if routing is maximally skewed
    got = moe_dispatch_combine(x, logits, wg, wu, wd, num_selected=k, capacity=s)
    want = _dense_reference(x, logits, wg, wu, wd, k)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_tokens_not_crashes():
    s, m, f, e = 64, 8, 12, 2
    x, logits, wg, wu, wd = _rand_moe(jax.random.PRNGKey(1), s, m, f, e)
    got = moe_dispatch_combine(x, logits, wg, wu, wd, num_selected=2, capacity=4)
    assert np.isfinite(np.asarray(got)).all()
    # with tiny capacity most tokens must be dropped → output mostly zeros
    dropped = (np.abs(np.asarray(got)).sum(-1) == 0).sum()
    assert dropped > 0


def test_moe_ep_sharded_matches_unsharded(cpu_mesh_devices):
    mesh = build_mesh(MeshConfig(dp=1, sp=1, ep=4, tp=2), devices=cpu_mesh_devices)
    s, m, f, e = 32, 16, 24, 4
    x, logits, wg, wu, wd = _rand_moe(jax.random.PRNGKey(2), s, m, f, e)
    plain = moe_dispatch_combine(x, logits, wg, wu, wd, num_selected=2, capacity=s)
    sharded = jax.jit(
        lambda *a: moe_dispatch_combine(
            *a, num_selected=2, capacity=s, mesh=mesh
        )
    )(x, logits, wg, wu, wd)
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(plain), rtol=1e-5, atol=1e-5
    )


def test_token_valid_keeps_padding_out_of_capacity():
    """Padding tokens must not consume expert capacity: real tokens' outputs
    with a mostly-padded batch == the same tokens alone at the same capacity."""
    s_real, pad, m, f, e = 8, 56, 8, 12, 2
    x, logits, wg, wu, wd = _rand_moe(jax.random.PRNGKey(8), s_real, m, f, e)
    cap = 8  # tight: 56 identical pad tokens would saturate both experts

    alone = moe_dispatch_combine(
        x, logits, wg, wu, wd, num_selected=2, capacity=cap
    )

    x_pad = jnp.concatenate([x, jnp.ones((pad, m), jnp.float32)])
    logits_pad = jnp.concatenate(
        [logits, jnp.full((pad, e), 5.0, jnp.float32)]
    )
    valid = jnp.arange(s_real + pad) < s_real
    padded = moe_dispatch_combine(
        x_pad, logits_pad, wg, wu, wd, num_selected=2, capacity=cap,
        token_valid=valid,
    )
    np.testing.assert_allclose(
        np.asarray(padded[:s_real]), np.asarray(alone), rtol=1e-5, atol=1e-5
    )
    # and the padding rows contribute nothing
    assert np.abs(np.asarray(padded[s_real:])).max() == 0.0


def test_dense_exact_matches_dispatch_at_full_capacity():
    s, m, f, e = 48, 16, 24, 4
    x, logits, wg, wu, wd = _rand_moe(jax.random.PRNGKey(7), s, m, f, e)
    from llmlb_tpu.ops.moe import moe_dense_exact

    dispatch = moe_dispatch_combine(x, logits, wg, wu, wd, num_selected=2, capacity=s)
    dense = moe_dense_exact(x, logits, wg, wu, wd, num_selected=2)
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(dispatch), rtol=1e-4, atol=1e-4
    )


def test_default_capacity():
    assert default_capacity(256, 8, 2) == 80  # 256*2/8*1.25
    assert default_capacity(4, 8, 1) >= 4


def test_mixtral_prefill_decode_consistency():
    """Prefill logits at position t == decode logits after feeding t tokens."""
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.models import mixtral

    cfg = get_preset("debug-moe-tiny")
    params = mixtral.init_params(cfg, jax.random.PRNGKey(3))
    b, t, cap = 2, 8, 16
    ids = jax.random.randint(jax.random.PRNGKey(4), (b, t), 0, cfg.vocab_size)
    lens = jnp.full((b,), t, jnp.int32)

    ck, cv, tables = identity_kv_pages(mixtral, cfg, b, cap)
    logits_p, ck, cv = mixtral.prefill_into_pages(params, cfg, ids, lens,
                                                  tables, ck, cv)

    # replay: prefill t-1 tokens then decode the t-th
    ck2, cv2, _ = identity_kv_pages(mixtral, cfg, b, cap)
    lens2 = jnp.full((b,), t - 1, jnp.int32)
    _, ck2, cv2 = mixtral.prefill_into_pages(
        params, cfg, ids[:, : t - 1], lens2, tables, ck2, cv2)
    logits_d, _, _ = mixtral.decode_step_paged(
        params, cfg, ids[:, t - 1], lens2, ck2, cv2, tables
    )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_d), rtol=5e-4, atol=5e-4
    )


def test_mixtral_ep_tp_sharded_serving_step(cpu_mesh_devices):
    """Full sharded Mixtral step on a dp=1 ep=4 tp=2 mesh == unsharded."""
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.models import mixtral

    cfg = get_preset("debug-moe-tiny")
    params = mixtral.init_params(cfg, jax.random.PRNGKey(5))
    mesh = build_mesh(MeshConfig(dp=1, sp=1, ep=4, tp=2), devices=cpu_mesh_devices)

    b, t, cap = 2, 8, 16
    ids = jax.random.randint(jax.random.PRNGKey(6), (b, t), 0, cfg.vocab_size)
    lens = jnp.full((b,), t, jnp.int32)

    ck, cv, tables = identity_kv_pages(mixtral, cfg, b, cap)
    want, _, _ = mixtral.prefill_into_pages(params, cfg, ids, lens, tables,
                                            ck, cv)

    shardings = mixtral.param_shardings(cfg, mesh)
    params_sh = {k: jax.device_put(v, shardings[k]) for k, v in params.items()}
    ck, cv, _ = identity_kv_pages(mixtral, cfg, b, cap)
    ck_sh, cv_sh = mixtral.kv_pages_shardings(cfg, mesh)
    ck, cv = jax.device_put(ck, ck_sh), jax.device_put(cv, cv_sh)
    got, ck, cv = mixtral.prefill_into_pages(params_sh, cfg, ids, lens,
                                             tables, ck, cv, mesh)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4
    )

    # and one decode step on the same sharded state
    tok = jnp.argmax(got, -1).astype(jnp.int32)
    logits_d, _, _ = mixtral.decode_step_paged(params_sh, cfg, tok, lens,
                                               ck, cv, tables, mesh)
    assert np.isfinite(np.asarray(logits_d)).all()
