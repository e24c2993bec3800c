"""ops/delta_rule.py on the CPU: the chunked form against the rule stepped
token by token (lengths that are no multiple of the chunk, a padded row, a
state carried in), the step kernel (interpret mode) against `jax.numpy`, in
place at (layer, slot) and bit for bit for a row that is not live, and the
pool's layout there and back."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops import delta_rule as dr

H, K, V = 3, 8, 16


def _inputs(seed, b, t, heads=H):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (b, t, heads, K)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * K**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, heads, V))
    g = -0.7 * jax.random.uniform(ks[3], (b, t, heads))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, heads)))
    s0 = jax.random.normal(ks[5], (b, heads, K, V))
    return q, k, v, g, beta, s0


def _token_by_token(q, k, v, g, beta, s0, lens):
    """The four lines of the module's docstring, a position at a time."""
    outs, s = [], s0
    for t in range(q.shape[1]):
        new = s * jnp.exp(g[:, t])[:, :, None, None]
        u = beta[:, t][:, :, None] * (
            v[:, t] - jnp.einsum("bhkv,bhk->bhv", new, k[:, t]))
        new = new + k[:, t][..., None] * u[:, :, None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", new, q[:, t]))
        s = jnp.where((t < lens)[:, None, None, None], new, s)
    return jnp.stack(outs, 1), s


@pytest.mark.parametrize("t,lens,chunk", [
    (150, (150, 97), 64),  # two chunks and a part; a row that ends inside one
    (40, (40, 1), 16),
    (16, (16, 16), 16),  # one whole chunk
    (7, (7, 3), 64),  # shorter than a chunk
])
def test_the_chunked_form_is_the_rule_stepped_token_by_token(t, lens, chunk):
    q, k, v, g, beta, s0 = _inputs(t, 2, t)
    lens = jnp.asarray(lens, jnp.int32)
    want_o, want_s = _token_by_token(q, k, v, g, beta, s0, lens)
    o, s = dr.delta_rule_chunked(q, k, v, g, beta, s0, lens, chunk=chunk)
    valid = (jnp.arange(t)[None, :] < lens[:, None])[..., None, None]
    np.testing.assert_allclose(jnp.where(valid, o, 0),
                               jnp.where(valid, want_o, 0), atol=5e-6)
    # the state after position lens - 1, whatever the padded length
    np.testing.assert_allclose(s, want_s, atol=5e-6)


def test_a_chunk_goes_on_from_the_state_the_last_one_left():
    q, k, v, g, beta, s0 = _inputs(5, 1, 50)
    lens = jnp.asarray([50], jnp.int32)
    whole_o, whole_s = dr.delta_rule_chunked(q, k, v, g, beta, s0, lens,
                                             chunk=16)
    cut = 23  # inside a chunk
    o1, s1 = dr.delta_rule_chunked(*(x[:, :cut] for x in (q, k, v, g, beta)),
                                   s0, jnp.asarray([cut]), chunk=16)
    o2, s2 = dr.delta_rule_chunked(*(x[:, cut:] for x in (q, k, v, g, beta)),
                                   s1, jnp.asarray([50 - cut]), chunk=16)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), whole_o,
                               atol=5e-6)
    np.testing.assert_allclose(s2, whole_s, atol=5e-6)


def test_the_pools_layout_folds_the_heads_into_the_value_axis():
    s = jax.random.normal(jax.random.PRNGKey(0), (2, 5, H, K, V))
    pool = dr.to_pool(s)
    assert pool.shape == (2, 5, K, H * V)
    # head h's values are columns [h V, (h + 1) V) of every key row
    np.testing.assert_array_equal(pool[1, 2, :, V:2 * V], s[1, 2, 1])
    np.testing.assert_array_equal(dr.from_pool(pool, H), s)
    # the published sizes: the minor dimension is whole lanes
    assert (30 * 192) % 128 == 0 and 96 % 8 == 0


@pytest.mark.parametrize("heads", [3, 4, 16])  # 16 x 16: two blocks of lanes
def test_the_step_kernel_is_the_rule_in_place_at_layer_and_slot(heads):
    layers, slots = 3, 5
    q, k, v, g, beta, _ = _inputs(heads, 1, slots, heads)
    q, k, v, alpha, beta = q[0], k[0], v[0], jnp.exp(g[0]), beta[0]
    pool = jax.random.normal(jax.random.PRNGKey(9),
                             (layers, slots, K, heads * V))
    live = jnp.asarray([True, False, True, True, False])
    want_o, want = dr.delta_rule_step(pool, 1, q, k, v, alpha, beta,
                                      live=live)  # jax.numpy on the CPU
    masked = (jnp.where(live[:, None, None], k, 0.0),
              jnp.where(live[:, None], alpha, 1.0),
              jnp.where(live[:, None], beta, 0.0))
    got, o = dr.delta_rule_decode_step(pool + 0, 1, q, masked[0], v,
                                       masked[1], masked[2], interpret=True)
    np.testing.assert_allclose(o[live], want_o[live], atol=2e-6)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the other layers and the rows that are not live: bit for bit
    for layer in (0, 2):
        np.testing.assert_array_equal(got[layer], pool[layer])
    np.testing.assert_array_equal(got[1][~live], pool[1][~live])
    np.testing.assert_array_equal(want[1][~live], pool[1][~live])
    # and the live rows are the four lines
    s = dr.from_pool(pool[1], heads) * alpha[:, :, None, None]
    u = beta[:, :, None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k))
    s = s + k[..., None] * u[:, :, None, :]
    np.testing.assert_allclose(dr.from_pool(got[1], heads)[live], s[live],
                               atol=2e-6)
    np.testing.assert_allclose(
        o[live], jnp.einsum("bhkv,bhk->bhv", s, q)[live], atol=2e-6)


def test_rows_in_named_slots_move_those_slots_alone():
    layers, slots = 2, 6
    q, k, v, g, beta, _ = _inputs(2, 1, 2)
    pool = jax.random.normal(jax.random.PRNGKey(3), (layers, slots, K, H * V))
    at = jnp.asarray([4, 1])
    o, got = dr.delta_rule_step(pool, 0, q[0], k[0], v[0], jnp.exp(g[0]),
                                beta[0], slots=at)
    others = np.setdiff1d(np.arange(slots), np.asarray(at))
    np.testing.assert_array_equal(got[0][others], pool[0][others])
    np.testing.assert_array_equal(got[1], pool[1])
    assert not np.allclose(got[0][at], pool[0][at]) and o.shape == (2, H, V)
    with pytest.raises(ValueError, match="say which slots"):
        dr.delta_rule_step(pool, 0, q[0], k[0], v[0], jnp.exp(g[0]), beta[0])
