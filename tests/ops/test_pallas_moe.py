"""The grouped expert matmul (ops/pallas_moe.py, interpret mode) against
`jax.lax.ragged_dot` on one layer's slice of the stack: even and skewed
loads, experts without rows, padding rows behind the experts', an expert
whose rows cross a tile boundary, the weights' columns tiled; the work-list
on hand-worked numbers; and the routed layer taking this path for a stack."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops import moe, pallas_moe


def _case(seed, experts, k, out, rows, layers=3, skew=5.0, padding=0):
    rng = np.random.default_rng(seed)
    load = rng.multinomial(rows - padding, rng.dirichlet(np.ones(experts) * skew))
    return (jnp.asarray(load, jnp.int32),
            jnp.asarray(rng.normal(size=(rows, k)), jnp.float32),
            jnp.asarray(rng.normal(size=(layers, experts, k, out)), jnp.float32))


def test_work_list_on_hand_worked_numbers():
    # tile 4: expert 0 has rows 0-2, expert 1 none, expert 2 rows 3-9,
    # expert 3 rows 10-10; 12 rows, the last one padding
    work = pallas_moe.group_work_list(jnp.asarray([3, 0, 7, 1]), rows=12,
                                      tile=4)
    n = int(work.count)
    assert n == 5  # (0, t0) (2, t0) (2, t1) (2, t2) (3, t2)
    assert np.asarray(work.expert_of)[:n].tolist() == [0, 2, 2, 2, 3]
    assert np.asarray(work.tile_of)[:n].tolist() == [0, 0, 1, 2, 2]
    assert np.asarray(work.row_lo)[:n].tolist() == [0, 3, 4, 8, 10]
    assert np.asarray(work.row_hi)[:n].tolist() == [3, 4, 8, 10, 11]
    assert np.asarray(work.first)[:n].tolist() == [1, 0, 1, 1, 0]
    assert work.expert_of.shape == (4 + 3,)  # X + tiles, static


@pytest.mark.parametrize("name,kw", [
    ("even", dict(experts=8, k=32, out=256, rows=96)),
    ("skewed_most_experts_empty", dict(experts=8, k=32, out=128, rows=96,
                                       skew=0.2)),
    ("padding_behind", dict(experts=4, k=16, out=128, rows=64, padding=20)),
    ("prefill_tile", dict(experts=8, k=64, out=384, rows=256)),
])
def test_kernel_matches_ragged_dot(name, kw):
    load, rows, w = _case(1, **kw)
    tile = 128 if kw["rows"] >= 256 else 32
    work = pallas_moe.group_work_list(load, rows=kw["rows"], tile=tile)
    for layer in (0, 2):
        got = pallas_moe.grouped_expert_matmul(rows, w, layer, work,
                                               tile=tile, interpret=True)
        want = jax.lax.ragged_dot(rows, w[layer], load)
        valid = int(load.sum())
        np.testing.assert_allclose(np.asarray(got[:valid]),
                                   np.asarray(want[:valid]),
                                   rtol=1e-5, atol=1e-5)


def test_columns_tile_when_a_matrix_outgrows_the_budget(monkeypatch):
    real = pallas_moe._column_tile
    monkeypatch.setattr(pallas_moe, "_column_tile",
                        lambda k, n, itemsize: real(k, n, itemsize,
                                                    budget=k * 128 * itemsize))
    pallas_moe.grouped_expert_matmul._clear_cache()
    load, rows, w = _case(2, experts=4, k=32, out=384, rows=64)
    work = pallas_moe.group_work_list(load, rows=64, tile=32)
    got = pallas_moe.grouped_expert_matmul(rows, w, 1, work, tile=32,
                                           interpret=True)
    pallas_moe.grouped_expert_matmul._clear_cache()
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.lax.ragged_dot(rows, w[1], load)),
                               rtol=1e-5, atol=1e-5)
    assert real(2048, 768, 2) == 768 and real(768, 2048, 2) == 2048
    assert real(4096, 14336, 2) == 512


def test_routed_layer_takes_the_kernel_for_a_stack(monkeypatch):
    """moe_routed with the experts stacked over layers and `layer`: the
    Pallas route (forced, interpreted) against the sliced ragged_dot route,
    padding tokens included."""
    s, m, f, e, layers = 24, 16, 128, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (s, m), jnp.float32)
    logits = jax.random.normal(ks[1], (s, e), jnp.float32)
    wg = jax.random.normal(ks[2], (layers, e, m, f), jnp.float32) * m**-0.5
    wu = jax.random.normal(ks[3], (layers, e, m, f), jnp.float32) * m**-0.5
    wd = jax.random.normal(ks[4], (layers, e, f, m), jnp.float32) * f**-0.5
    valid = jnp.arange(s) < 20
    route = lambda r: moe.top_k_routing(r, 2)  # noqa: E731
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "xla")
    want, _ = moe.moe_routed(x, logits, wg, wu, wd, route=route, layer=1,
                             token_valid=valid)
    sliced, _ = moe.moe_routed(x, logits, wg[1], wu[1], wd[1], route=route,
                               token_valid=valid)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(sliced))
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    got, routing = moe.moe_routed(x, logits, wg, wu, wd, route=route, layer=1,
                                  token_valid=valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(got[20:])).max() == 0.0
    assert int(routing.load.sum()) == 20 * 2
