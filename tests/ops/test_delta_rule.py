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


# --- a decay a KEY CHANNEL (Kimi Delta Attention) ----------------------------

def _vector(g, seed=0, spread=1.0):
    """`g` [B, T, H] -> [B, T, H, K]: each channel its own share of it."""
    u = jax.random.uniform(jax.random.PRNGKey(seed), (*g.shape, K))
    return g[..., None] * (1.0 + spread * (u - 0.5))


def _token_by_token_vector(q, k, v, g, beta, s0, lens):
    """S <- Diag(a_t) S; u = b_t (v_t - S^T k_t); S <- S + k_t u^T; o_t =
    S^T q_t, a position at a time, `g` [B, T, H, K]."""
    outs, s = [], s0
    for t in range(q.shape[1]):
        new = s * jnp.exp(g[:, t])[..., None]
        u = beta[:, t][:, :, None] * (
            v[:, t] - jnp.einsum("bhkv,bhk->bhv", new, k[:, t]))
        new = new + k[:, t][..., None] * u[:, :, None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", new, q[:, t]))
        s = jnp.where((t < lens)[:, None, None, None], new, s)
    return jnp.stack(outs, 1), s


@pytest.mark.parametrize("t,lens,chunk,decay", [
    (150, (150, 97), 64, "spread"),  # four sub-chunks of 16 a chunk
    (150, (150, 97), 64, "hard"),  # g = -1.6 a token over whole chunks
    (70, (70, 33), 64, "mixed"),  # -1.6 beside -0.001, channel by channel
    (40, (40, 1), 16, "spread"),  # a chunk is one sub-chunk
    (40, (40, 9), 32, "hard"),  # two sub-chunks
    (21, (21, 8), 8, "mixed"),  # a chunk no multiple of the sub-chunk
    (7, (7, 3), 64, "spread"),  # shorter than a chunk
])
def test_the_vector_decay_chunked_form_is_the_rule_token_by_token(
        t, lens, chunk, decay):
    """And finite where e^{-G} is not: at g = -1.6 a token over a chunk of
    64, e^{-G} reaches e^{102} and float32 ends at e^{88.7}."""
    q, k, v, g, beta, s0 = _inputs(t, 2, t)
    g = {"spread": _vector(g, t),
         "hard": jnp.full((*g.shape, K), -1.6),
         "mixed": jnp.where(_vector(g, t) < g[..., None], -1.6, -1e-3)}[decay]
    lens = jnp.asarray(lens, jnp.int32)
    want_o, want_s = _token_by_token_vector(q, k, v, g, beta, s0, lens)
    o, s = dr.delta_rule_chunked(q, k, v, g, beta, s0, lens, chunk=chunk)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(s)).all()
    valid = (jnp.arange(t)[None, :] < lens[:, None])[..., None, None]
    np.testing.assert_allclose(jnp.where(valid, o, 0),
                               jnp.where(valid, want_o, 0), atol=5e-6)
    np.testing.assert_allclose(s, want_s, atol=5e-6)


def test_the_obvious_factorisation_overflows_where_this_form_does_not():
    """The trap the form avoids: (k e^G) . (k e^-G) at g = -1.6 over 64."""
    cum = jnp.cumsum(jnp.full((1, 1, 64, K), -1.6), axis=-2)
    assert not np.isfinite(np.asarray(jnp.exp(-cum))).all()
    kc = jnp.ones((1, 1, 64, K)) * K**-0.5
    (kk,) = dr._pair_products((kc,), kc, cum, dr.SUB)
    assert np.isfinite(np.asarray(kk)).all()
    i, j = np.tril_indices(64)
    np.testing.assert_allclose(np.asarray(kk)[0, 0, i, j],
                               np.exp(-1.6 * (i - j)), rtol=1e-5, atol=1e-12)
    assert (np.asarray(kk)[0, 0][np.triu_indices(64, 1)] == 0).all()


def test_a_vector_chunk_goes_on_from_the_state_the_last_one_left():
    q, k, v, g, beta, s0 = _inputs(5, 1, 50)
    g = _vector(g, 5)
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


@pytest.mark.parametrize("heads", [3, 4, 16])  # 16 x 16: two blocks of lanes
def test_the_vector_decay_step_kernel_is_the_rule_in_place(heads):
    layers, slots = 3, 5
    q, k, v, g, beta, _ = _inputs(heads, 1, slots, heads)
    q, k, v, beta = q[0], k[0], v[0], beta[0]
    alpha = jnp.exp(_vector(g, heads)[0])  # [slots, heads, K]
    pool = jax.random.normal(jax.random.PRNGKey(9),
                             (layers, slots, K, heads * V))
    live = jnp.asarray([True, False, True, True, False])
    want_o, want = dr.delta_rule_step(pool, 1, q, k, v, alpha, beta,
                                      live=live)  # jax.numpy on the CPU
    masked = (jnp.where(live[:, None, None], k, 0.0),
              jnp.where(live[:, None, None], alpha, 1.0),
              jnp.where(live[:, None], beta, 0.0))
    got, o = dr.delta_rule_decode_step(pool + 0, 1, q, masked[0], v,
                                       masked[1], masked[2], interpret=True)
    np.testing.assert_allclose(o[live], want_o[live], atol=2e-6)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for layer in (0, 2):
        np.testing.assert_array_equal(got[layer], pool[layer])
    np.testing.assert_array_equal(got[1][~live], pool[1][~live])
    np.testing.assert_array_equal(want[1][~live], pool[1][~live])
    # and the live rows are the four lines, the decay a scale of S's ROWS
    s = dr.from_pool(pool[1], heads) * alpha[..., None]
    u = beta[:, :, None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k))
    s = s + k[..., None] * u[:, :, None, :]
    np.testing.assert_allclose(dr.from_pool(got[1], heads)[live], s[live],
                               atol=2e-6)
    np.testing.assert_allclose(
        o[live], jnp.einsum("bhkv,bhk->bhv", s, q)[live], atol=2e-6)


def test_a_decay_of_alpha_times_one_is_the_scalar_rule_bit_for_bit():
    """Olmo's rule is the case a_t = alpha_t 1: the step gives the same bits
    either way (`jax.numpy` and the kernel), the chunked form the same
    numbers; and the scalar case's program is the one it was before the
    rule took a vector (its results on the CPU, pinned)."""
    import hashlib

    q, k, v, g, beta, s0 = _inputs(11, 2, 40)
    lens = jnp.asarray([40, 23], jnp.int32)
    wide = jnp.broadcast_to(g[..., None], (*g.shape, K))
    o, s = dr.delta_rule_chunked(q, k, v, g, beta, s0, lens, chunk=16)
    wide_o, wide_s = dr.delta_rule_chunked(q, k, v, wide, beta, s0, lens,
                                           chunk=16)
    np.testing.assert_allclose(wide_o, o, atol=5e-6)
    np.testing.assert_allclose(wide_s, s, atol=5e-6)
    pool = jnp.zeros((2, 2, K, H * V)).at[1].set(dr.to_pool(s0))
    step = (q[:, 0], k[:, 0], v[:, 0])
    alpha = jnp.exp(g[:, 0])
    o1, p1 = dr.delta_rule_step(pool, 1, *step, alpha, beta[:, 0])
    o2, p2 = dr.delta_rule_step(pool, 1, *step, jnp.exp(wide[:, 0]),
                                beta[:, 0])
    np.testing.assert_array_equal(o1, o2)
    np.testing.assert_array_equal(p1, p2)
    p3, o3 = dr.delta_rule_decode_step(pool + 0, 1, *step, alpha, beta[:, 0],
                                       interpret=True)
    p4, o4 = dr.delta_rule_decode_step(pool + 0, 1, *step,
                                       jnp.exp(wide[:, 0]), beta[:, 0],
                                       interpret=True)
    np.testing.assert_array_equal(o3, o4)
    np.testing.assert_array_equal(p3, p4)

    def digest(x):
        return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]

    assert {"chunked_o": digest(o), "chunked_s": digest(s),
            "step_o": digest(o1), "step_pool": digest(p1),
            "kernel_o": digest(o3), "kernel_pool": digest(p3)} == SCALAR_BITS


# what the parent commit (PR 61) gives for the inputs of the test above,
# here on the CPU: scripts-free, read by running the same lines on its tree
SCALAR_BITS = {"chunked_o": "75acb576e88b6f0a", "chunked_s": "2213192a2cdf0ed4",
               "step_o": "6c044dabbc26e55c", "step_pool": "3f31fa04faeae33b",
               "kernel_o": "77a4bbe000b50a76", "kernel_pool": "272182386d449d01"}
