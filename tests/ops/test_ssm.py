"""The state-space ops (ops/ssm.py) against the plain per-token recurrence:
`ssd_chunked` at lengths that are not a multiple of the chunk, with and
without an initial state, rows of unlike lengths in one call; `ssm_step`
(the Pallas kernel in interpret mode and the `jax.numpy` route) continuing
a scan; rows that are not live keeping their state; the convolution's
carried rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops import ssm

H, P, G, N = 8, 8, 2, 16


def _inputs(seed, b, t):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    return dict(
        x=f(b, t, H, P), dt=jax.nn.softplus(f(b, t, H) - 2.0),
        a=-jnp.exp(jnp.asarray(r.uniform(0, 2.7, H), jnp.float32)),
        b=f(b, t, G, N), c=f(b, t, G, N), d=f(H), s0=f(b, H, P, N))


def _recurrence(x, dt, a, b, c, d, s0, lens):
    """Token by token, row by row, in numpy float64, at any heads and
    groups (a group's B and C repeated over its heads)."""
    x, dt, a, b, c, d, s = (np.asarray(v, np.float64)
                            for v in (x, dt, a, b, c, d, s0))
    share = x.shape[2] // b.shape[2]
    ys = np.zeros(x.shape)
    for row in range(x.shape[0]):
        for t in range(int(lens[row])):
            bh = np.repeat(b[row, t], share, axis=0)
            ch = np.repeat(c[row, t], share, axis=0)
            s[row] = (np.exp(dt[row, t] * a)[:, None, None] * s[row]
                      + (dt[row, t][:, None] * x[row, t])[:, :, None]
                      * bh[:, None, :])
            ys[row, t] = (s[row] * ch[:, None, :]).sum(-1) + d[:, None] * x[row, t]
    return ys, s


@pytest.mark.parametrize("t,lens,initial", [
    (16, [16, 16], False),   # one whole chunk
    (37, [37, 21], True),    # ends inside the third chunk; a shorter row
    (37, [5, 37], False),    # shorter than one chunk
    (48, [48, 33], True),    # whole chunks, a row that ends inside one
])
def test_chunked_scan_equals_the_per_token_recurrence(t, lens, initial):
    v = _inputs(t, 2, t)
    if not initial:
        v["s0"] = jnp.zeros_like(v["s0"])
    lens = jnp.asarray(lens, jnp.int32)
    y, s = ssm.ssd_chunked(**v, lens=lens, chunk=16)
    want_y, want_s = _recurrence(**v, lens=lens)
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(y)[row, :n], want_y[row, :n],
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-4, atol=2e-4)


def _step_case(seed=5, slots=4, layers=3):
    v = _inputs(seed, slots, 1)
    pool = jnp.asarray(np.random.default_rng(seed).normal(
        size=(layers, slots, H, P, N)), jnp.float32)
    args = (v["x"][:, 0], v["dt"][:, 0], v["a"], v["b"][:, 0], v["c"][:, 0],
            v["d"])
    return v, pool, args


def test_the_step_continues_the_scan_and_touches_its_layer_alone():
    v, pool, args = _step_case()
    y, new = ssm.ssm_step(*args, pool + 0, 1)
    want_y, want_s = _recurrence(**{**v, "s0": pool[1]}, lens=[1] * 4)
    np.testing.assert_allclose(np.asarray(y), want_y[:, 0], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[1]), want_s, rtol=1e-5,
                               atol=1e-5)
    assert (np.asarray(new[0]) == np.asarray(pool[0])).all()
    assert (np.asarray(new[2]) == np.asarray(pool[2])).all()


def test_rows_at_named_slots_leave_the_other_slots_alone():
    _v, pool, args = _step_case(seed=7)
    two = tuple(v[:2] if v.ndim and v.shape[0] == 4 else v for v in args)
    slots = jnp.asarray([3, 1])
    _y, new = ssm.ssm_step(*two, pool + 0, 0, slots=slots)
    assert (np.asarray(new[0, 0]) == np.asarray(pool[0, 0])).all()
    assert (np.asarray(new[0, 2]) == np.asarray(pool[0, 2])).all()
    _y, whole = ssm.ssm_step(*args, pool + 0, 0)
    # row 0 of the two went to slot 3 with row 0's inputs
    x, dt, a, b, c, d = two
    want = _recurrence(x[:, None], dt[:, None], a, b[:, None], c[:, None], d,
                       pool[0, slots], [1, 1])[1]
    np.testing.assert_allclose(np.asarray(new[0, slots]), want, rtol=1e-5,
                               atol=1e-5)
    with pytest.raises(ValueError, match="say which slots"):
        ssm.ssm_step(*two, pool, 0)
    del whole


def test_the_convolution_carries_the_rows_that_end_at_the_length():
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(2, 9, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(6, 4)), jnp.float32)
    b = jnp.asarray(r.normal(size=(6,)), jnp.float32)
    lens = jnp.asarray([9, 5], jnp.int32)
    out, carry = ssm.causal_conv(x, jnp.zeros((2, 3, 6)), w, b, lens)
    padded = np.concatenate([np.zeros((2, 3, 6)), np.asarray(x)], axis=1)
    want = sum(padded[:, j:j + 9] * np.asarray(w)[:, j] for j in range(4)) + np.asarray(b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax.nn.silu(want)),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(carry[0]) == np.asarray(x[0, 6:9])).all()
    assert (np.asarray(carry[1]) == np.asarray(x[1, 2:5])).all()
    # the next chunk, with the rows carried, equals the convolution unbroken
    more = jnp.asarray(r.normal(size=(2, 4, 6)), jnp.float32)
    nxt, _ = ssm.causal_conv(more[:1], carry[:1], w, b, jnp.asarray([4]))
    whole, _ = ssm.causal_conv(jnp.concatenate([x[:1], more[:1]], axis=1),
                               jnp.zeros((1, 3, 6)), w, b, jnp.asarray([13]))
    np.testing.assert_allclose(np.asarray(nxt), np.asarray(whole[:, 9:]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lens", [[9, 5], [1, 2], [3, 9]])
def test_a_convolution_without_bias_or_activation_is_the_bare_sum(lens):
    """What a gated short convolution asks of `causal_conv` (models/
    lfm2_moe.py): three taps, `b` None, `act` None — the taps' sum and
    nothing else, the two carried rows those that end at the length, at
    lengths under the taps too (a row of the zeros in front is then still
    carried)."""
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(2, 9, 6)), jnp.float32)
    prev = jnp.asarray(r.normal(size=(2, 2, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(6, 3)), jnp.float32)
    out, carry = ssm.causal_conv(x, prev, w, None, jnp.asarray(lens),
                                 act=None)
    padded = np.concatenate([np.asarray(prev), np.asarray(x)], axis=1)
    want = sum(padded[:, j:j + 9] * np.asarray(w)[:, j] for j in range(3))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    for row, n in enumerate(lens):
        assert (np.asarray(carry[row]) == padded[row, n:n + 2]).all()
    # each half of what is optional alone
    biased, _ = ssm.causal_conv(x, prev, w, jnp.ones((6,)), jnp.asarray(lens),
                                act=None)
    np.testing.assert_allclose(np.asarray(biased), want + 1, rtol=1e-5,
                               atol=1e-5)
    acted, _ = ssm.causal_conv(x, prev, w, None, jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(acted),
                               np.asarray(jax.nn.silu(want)), rtol=1e-5,
                               atol=1e-5)


def test_a_mamba_callers_convolution_traces_what_it_did_before_pr_59():
    """`causal_conv` as it stood while its bias and its silu were not
    optional, written out: a caller that passes both positionally and says
    nothing of `act` (models/nemotron_h.ssm_mixer, models/olmo_hybrid.py)
    gets the same jaxpr, equation for equation."""
    def as_it_was(x, prev, w, b, lens):
        width = w.shape[-1]
        t = x.shape[1]
        padded = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
        out = sum(padded[:, j:j + t].astype(ssm.F32) * w[:, j].astype(ssm.F32)
                  for j in range(width)) + b.astype(ssm.F32)
        at = lens[:, None] + jnp.arange(width - 1, dtype=lens.dtype)[None, :]
        carry = jnp.take_along_axis(padded, at[:, :, None], axis=1)
        return jax.nn.silu(out).astype(x.dtype), carry

    args = (jnp.zeros((2, 9, 6), jnp.bfloat16), jnp.zeros((2, 3, 6)),
            jnp.zeros((6, 4), jnp.bfloat16), jnp.zeros((6,), jnp.bfloat16),
            jnp.asarray([9, 5], jnp.int32))
    assert str(jax.make_jaxpr(ssm.causal_conv)(*args)) == str(
        jax.make_jaxpr(as_it_was)(*args))


# --- the step kernel at every grouping: ONE group for all the heads
# (Granite-4.0-H, PR 55), a group a head, and between (PR 56) ----------------

def _grouped(seed, b, t, heads=64, p=64, groups=1, n=16):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    return dict(
        x=f(b, t, heads, p), dt=jax.nn.softplus(f(b, t, heads) - 2.0),
        a=-jnp.exp(jnp.asarray(r.uniform(0, 2.7, heads), jnp.float32)),
        b=f(b, t, groups, n), c=f(b, t, groups, n), d=f(heads),
        s0=f(b, heads, p, n))


@pytest.mark.parametrize("live", [None, [True, False, True]],
                         ids=["all_live", "a_row_not_live"])
@pytest.mark.parametrize("heads,p", [(8, 16), (64, 64)],
                         ids=["128_channels", "4096_channels"])
@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_kernel_equals_the_plain_route_and_a_row_not_live_stays(
        groups, heads, p, live):
    """`ssm_decode_step` (interpret) against `ssm_step`'s jax.numpy arm and
    the per-token recurrence: at H P = 128 channels (ONE turn of the
    kernel's loop, which then holds every group) and at 4,096 (eight turns;
    a turn holds one group, or half of one), at one group, two and eight.
    The state within 1e-6 of the plain arm's (the same two products and one
    sum an element; on this backend the PARENT's kernel did not agree with
    that arm bit for bit in any of these cases either, so none asserts it),
    y within 1e-5, the other layer and a row not live bit for bit."""
    v = _grouped(11 + groups, 3, 1, heads=heads, p=p, groups=groups)
    pool = jnp.asarray(np.random.default_rng(12).normal(
        size=(2, 3, heads, p, 16)), jnp.float32)
    x, dt, b, c = v["x"][:, 0], v["dt"][:, 0], v["b"][:, 0], v["c"][:, 0]
    live = None if live is None else jnp.asarray(live)
    y, new = ssm.ssm_step(x, dt, v["a"], b, c, v["d"], pool + 0, 1,
                          slots=jnp.arange(3), live=live)  # no kernel
    decay, dtx = ssm._step_inputs(x, dt, v["a"], live)
    k_pool, k_sc = ssm.ssm_decode_step(pool + 0, 1, decay, dtx, b, c,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(k_pool), np.asarray(new),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(k_sc + x * v["d"][:, None]),
                               np.asarray(y), rtol=1e-5, atol=1e-5)
    assert (np.asarray(k_pool[0]) == np.asarray(pool[0])).all()
    rows = [0, 2] if live is not None else [0, 1, 2]
    want_y, want_s = _recurrence(**{**v, "s0": pool[1]}, lens=[1, 1, 1])
    np.testing.assert_allclose(np.asarray(y)[rows], want_y[rows, 0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[1])[rows], want_s[rows],
                               rtol=1e-5, atol=1e-5)
    for got in (new, k_pool):
        for row in rows:
            assert (np.asarray(got[1, row]) != np.asarray(pool[1, row])).any()
        if live is not None:  # bit for bit
            assert (np.asarray(got[1, 1]) == np.asarray(pool[1, 1])).all()


@pytest.mark.parametrize("t,lens,initial", [
    (256, [256, 200], False),  # one whole chunk of the published 256
    (300, [300, 41], True),    # ends inside the second; shorter than one
])
def test_the_scan_at_chunks_of_256_and_one_group_equals_the_recurrence(
        t, lens, initial):
    v = _grouped(t, 2, t, heads=8, p=8)
    if not initial:
        v["s0"] = jnp.zeros_like(v["s0"])
    lens = jnp.asarray(lens, jnp.int32)
    y, s = ssm.ssd_chunked(**v, lens=lens, chunk=256)
    want_y, want_s = _recurrence(**v, lens=lens)
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(y)[row, :n], want_y[row, :n],
                                   rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=5e-4, atol=5e-4)
