"""The routed expert layer (ops/moe.py) + Mixtral model: correctness vs an
every-expert-on-every-token sum under both routing rules, skewed routing,
padding, expert-parallel sharding equivalence on the virtual 8-device CPU
mesh (SURVEY.md §4 strategy)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops.moe import moe_routed, sigmoid_bias_routing, top_k_routing
from llmlb_tpu.parallel.mesh import MeshConfig, build_mesh
from tests.support import identity_kv_pages


def _softmax_rule(k):
    return lambda logits: top_k_routing(logits, k)


def _sigmoid_rule(k, bias, scale=2.448):
    return lambda logits: sigmoid_bias_routing(logits, bias, k, scale=scale)


def _every_expert_reference(x, logits, wg, wu, wd, route):
    """Every expert on every token, summed by the rule's weights: no
    sorting, no groups, no capacity."""
    weights, idx, *_ = route(jnp.asarray(logits, jnp.float32))
    weights, idx = np.asarray(weights), np.asarray(idx)
    x, wg, wu, wd = (np.asarray(a, np.float64) for a in (x, wg, wu, wd))
    out = np.zeros_like(x)
    for e in range(wg.shape[0]):
        h = x @ wg[e]
        y = ((h / (1 + np.exp(-h))) * (x @ wu[e])) @ wd[e]  # [S, M]
        out += np.where(idx == e, weights, 0.0).sum(-1)[:, None] * y
    return out


def _rand_moe(key, s, m, f, e):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (s, m), jnp.float32)
    logits = jax.random.normal(ks[1], (s, e), jnp.float32)
    wg = jax.random.normal(ks[2], (e, m, f), jnp.float32) * m**-0.5
    wu = jax.random.normal(ks[3], (e, m, f), jnp.float32) * m**-0.5
    wd = jax.random.normal(ks[4], (e, f, m), jnp.float32) * f**-0.5
    bias = jax.random.normal(ks[5], (e,), jnp.float32) * 0.3
    return x, logits, wg, wu, wd, bias


RULES = {
    "softmax_k1": lambda bias: _softmax_rule(1),
    "softmax_k2": lambda bias: _softmax_rule(2),
    "sigmoid_bias_k3": lambda bias: _sigmoid_rule(3, bias),
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_routed_matches_every_expert_sum(rule):
    s, m, f, e = 32, 16, 24, 8
    x, logits, wg, wu, wd, bias = _rand_moe(jax.random.PRNGKey(0), s, m, f, e)
    route = RULES[rule](bias)
    got, routing = moe_routed(x, logits, wg, wu, wd, route=route)
    want = _every_expert_reference(x, logits, wg, wu, wd, route)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    k = routing.chosen.shape[-1]
    assert int(routing.load.sum()) == s * k  # every assignment, none dropped


@pytest.mark.parametrize("rule", ["softmax_k2", "sigmoid_bias_k3"])
def test_routed_skewed_to_one_expert_drops_nothing(rule):
    """All tokens' first choice is expert 2: a capacity dispatch would drop
    most of them, the grouped products take them all."""
    s, m, f, e = 48, 8, 12, 4
    x, logits, wg, wu, wd, bias = _rand_moe(jax.random.PRNGKey(1), s, m, f, e)
    logits = logits.at[:, 2].set(9.0)
    route = RULES[rule](bias * 0)
    got, routing = moe_routed(x, logits, wg, wu, wd, route=route)
    want = _every_expert_reference(x, logits, wg, wu, wd, route)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert int(routing.load[2]) == s


@pytest.mark.parametrize("rule", ["softmax_k2", "sigmoid_bias_k3"])
def test_routed_padding_is_zero_and_outside_the_load(rule):
    """Padding tokens belong to no expert's group: the real tokens' outputs
    are what they are alone, padding rows come out as zeros and no expert's
    load counts them."""
    s_real, pad, m, f, e = 8, 56, 8, 12, 4
    x, logits, wg, wu, wd, bias = _rand_moe(jax.random.PRNGKey(8), s_real, m,
                                            f, e)
    route = RULES[rule](bias)
    alone, routing_alone = moe_routed(x, logits, wg, wu, wd, route=route)
    x_pad = jnp.concatenate([x, jnp.ones((pad, m), jnp.float32)])
    logits_pad = jnp.concatenate(
        [logits, jnp.full((pad, e), 5.0, jnp.float32)])
    valid = jnp.arange(s_real + pad) < s_real
    padded, routing = moe_routed(x_pad, logits_pad, wg, wu, wd, route=route,
                                 token_valid=valid)
    np.testing.assert_allclose(
        np.asarray(padded[:s_real]), np.asarray(alone), rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(padded[s_real:])).max() == 0.0
    np.testing.assert_array_equal(np.asarray(routing.load),
                                  np.asarray(routing_alone.load))


def test_sigmoid_rule_chooses_by_bias_and_weighs_without_it():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]], jnp.float32)
    bias = jnp.asarray([-5.0, 0.0, 0.0, 5.0], jnp.float32)
    w, idx, biased = sigmoid_bias_routing(logits, bias, 2, scale=2.0)
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    assert sorted(np.asarray(idx)[0].tolist()) == [1, 3]  # 0 is biased out
    picked = s[np.asarray(idx)[0]]
    np.testing.assert_allclose(np.asarray(w)[0], picked / picked.sum() * 2.0,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(biased)[0], s + np.asarray(bias),
                               rtol=1e-6)


@pytest.mark.parametrize("eps", [None, 1e-6, 0.5])
def test_the_biased_rules_normalise_over_the_sum_and_their_own_epsilon(eps):
    """`eps` under the chosen scores' sum is the family's own: DeepSeek-V3's
    1e-20 where nothing is said (the four families on the rule do not
    move), 1e-6 in models/lfm2_moe.py; 0.5 shows that it is read."""
    from llmlb_tpu.ops.moe import softmax_bias_routing

    logits = jnp.asarray([[2.0, -1.0, 0.5, 1.0]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.1, 0.0], jnp.float32)
    kw = {} if eps is None else {"eps": eps}
    w, idx, _ = sigmoid_bias_routing(logits, bias, 2, scale=2.0, **kw)
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    picked = s[np.asarray(idx)[0]]
    want = 2.0 * picked / (picked.sum() + (1e-20 if eps is None else eps))
    np.testing.assert_allclose(np.asarray(w)[0], want, rtol=1e-6)
    if eps == 0.5:
        assert np.asarray(w).sum() < 2.0 * 0.8
    # the softmax rule shares the choice and keeps the default
    w_soft, _, _ = softmax_bias_routing(logits, bias, 2, normalize=True)
    np.testing.assert_allclose(np.asarray(w_soft).sum(), 1.0, rtol=1e-6)
    # said or not, 1e-20 traces the same program
    assert str(jax.make_jaxpr(lambda l: sigmoid_bias_routing(l, bias, 2))(
        logits)) == str(jax.make_jaxpr(lambda l: sigmoid_bias_routing(
            l, bias, 2, eps=1e-20))(logits))


def test_routed_int8_scales_apply_per_row_expert():
    s, m, f, e = 16, 8, 12, 4
    x, logits, wg, wu, wd, _ = _rand_moe(jax.random.PRNGKey(3), s, m, f, e)
    route = _softmax_rule(2)
    ones = {"w_gate_scale": jnp.full((e, f), 2.0), "w_up_scale":
            jnp.full((e, f), 0.5), "w_down_scale": jnp.full((e, m), 3.0)}
    plain, _ = moe_routed(x, logits, wg * 2.0, wu * 0.5, wd * 3.0, route=route)
    scaled, _ = moe_routed(x, logits, wg, wu, wd, route=route, **ones)
    np.testing.assert_allclose(np.asarray(scaled), np.asarray(plain),
                               rtol=1e-4, atol=1e-4)


def test_moe_ep_sharded_matches_unsharded(cpu_mesh_devices):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh(MeshConfig(dp=1, sp=1, ep=4, tp=2), devices=cpu_mesh_devices)
    s, m, f, e = 32, 16, 24, 4
    x, logits, wg, wu, wd, _ = _rand_moe(jax.random.PRNGKey(2), s, m, f, e)
    fn = functools.partial(moe_routed, route=_softmax_rule(2))
    plain, _ = fn(x, logits, wg, wu, wd)
    ep = NamedSharding(mesh, P("ep", None, None))
    sharded, _ = jax.jit(fn)(x, logits, *(jax.device_put(w, ep)
                                          for w in (wg, wu, wd)))
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(plain), rtol=1e-5, atol=1e-5
    )


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
