"""Learned sparse attention's ops (ops/attention.py, ops/pallas_attention.py;
docs/sparse-attention.md) on the CPU: the exact top-k with ties to the lower
position, the indexer's scores, and the two decode kernels (interpreted)
against the plain einsums they stand for."""

import inspect

import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops import attention, pallas_attention as kernels


def _by_sort(scores, valid, k):
    """The top-k by a stable sort: the definition topk_mask is held to."""
    order = np.argsort(np.where(valid, -scores.astype(np.float64), np.inf),
                       axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    return valid & (rank < k)


@pytest.mark.parametrize("k", [1, 3, 7, 16, 40])
@pytest.mark.parametrize("case", ["normal", "ties", "signs", "short"])
def test_the_topk_is_exact_and_ties_go_to_the_lower_position(case, k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(3, 5, 33)).astype(np.float32)
    valid = np.arange(33)[None, None, :] <= rng.integers(0, 33, (3, 5, 1))
    if case == "ties":  # a handful of values: most of the top-k are ties
        scores = rng.integers(-2, 3, size=scores.shape).astype(np.float32)
    elif case == "signs":  # zeros of both signs, tiny (normal) and huge magnitudes
        scores = rng.choice(np.asarray(
            [0.0, -0.0, 1e-37, -1e-37, 3e38, -3e38, 1.0, -1.0], np.float32),
            size=scores.shape)
    elif case == "short":
        valid = np.arange(33)[None, None, :] < 2
    got = np.asarray(attention.topk_mask(jnp.asarray(scores),
                                         jnp.asarray(valid), k))
    np.testing.assert_array_equal(got, _by_sort(scores, valid, k))
    assert (got.sum(-1) == np.minimum(valid.sum(-1), k)).all()


def test_all_equal_scores_choose_the_first_k_positions():
    got = np.asarray(attention.topk_mask(
        jnp.zeros((1, 1, 12)), jnp.arange(12)[None, None] < 9, 4))
    assert got[0, 0].tolist() == [True] * 4 + [False] * 8


def test_index_scores_are_the_weighted_relu_sum_block_by_block(monkeypatch):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 3, 4, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 3, 4)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 24, 8)), jnp.float32)
    want = np.einsum("bhts,bth->bts", np.maximum(
        np.einsum("bthd,bsd->bhts", q, k), 0), w)
    np.testing.assert_allclose(np.asarray(attention.index_scores(q, w, k)),
                               want, atol=1e-5)
    monkeypatch.setattr(attention, "INDEX_KEY_BLOCK", 8)  # three blocks
    np.testing.assert_allclose(np.asarray(attention.index_scores(q, w, k)),
                               want, atol=1e-5)


def _pools(rng, layers=2, pages=9, ps=8, c=32, di=16):
    c_pages = jnp.asarray(rng.normal(size=(layers, pages, ps, c)), jnp.float32)
    r = rng.normal(size=(layers, pages, ps, 128 + di)).astype(np.float32)
    r[..., 8:128] = 0  # a rope of 8 numbers in its tile, then the index key
    return c_pages, jnp.asarray(r)


@pytest.mark.parametrize("pages", [None, 3])
def test_the_index_score_kernel_reads_the_keys_behind_the_ropes_tile(pages):
    rng = np.random.default_rng(1)
    _, r_pages = _pools(rng, di=128)
    tables = jnp.asarray(rng.permutation(8)[:8].reshape(2, 4) + 1, jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 4, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 4)), jnp.float32)
    got = kernels.index_scores_decode(q, w, r_pages, 1, tables, pages=pages,
                                      interpret=True)
    swept = tables[:, :pages] if pages else tables
    keys = attention.gather_kv_pages(r_pages, swept, layer=1)[..., 128:]
    want = attention.index_scores(q[:, None], w[:, None], keys)[:, 0]
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("lens", [(29, 7), (32, 0), (1, 17)])
def test_the_sparse_decode_kernel_attends_over_the_chosen_cells_alone(
        monkeypatch, lens):
    """The interpreted kernel against the masked einsum, at lengths that end
    inside a page, on a page's end, at one cell and at a row not live."""
    rng = np.random.default_rng(2)
    c_pages, r_pages = _pools(rng)
    tables = jnp.asarray(rng.permutation(8).reshape(2, 4) + 1, jnp.int32)
    q_abs = jnp.asarray(rng.normal(size=(2, 1, 4, 32)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(2, 1, 4, 8)), jnp.float32)
    kv_lens = jnp.asarray(lens, jnp.int32)
    chosen = jnp.asarray(rng.random((2, 1, 32)) < 0.4)
    chosen = chosen.at[:, :, 0].set(True)  # never an empty softmax
    kw = dict(scale=0.17, window=None)
    want = attention.paged_latent_decode(
        q_abs, q_rope, c_pages, r_pages, 1, tables, kv_lens, selected=chosen,
        **kw)
    assert attention.traced_routes()["sparse_latent_decode"] == "xla"
    got = kernels.sparse_latent_decode(
        q_abs[:, 0], attention._pad_last(q_rope[:, 0], 128), c_pages, r_pages,
        1, tables, kv_lens, chosen[:, 0], scale=0.17, interpret=True)
    live = np.asarray(kv_lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want[:, 0])[live], atol=1e-5)
    assert (np.asarray(got)[~live] == 0).all()
    # nothing outside the selection contributes: garbage there changes nothing
    mask = np.asarray(chosen[:, 0]).reshape(2, 4, 8)
    dirty = np.asarray(c_pages).copy()
    for b in range(2):
        for j in range(4):
            dirty[1, int(tables[b, j])][~mask[b, j]] = 1e4
    again = kernels.sparse_latent_decode(
        q_abs[:, 0], attention._pad_last(q_rope[:, 0], 128),
        jnp.asarray(dirty), r_pages, 1, tables, kv_lens, chosen[:, 0],
        scale=0.17, interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_a_selection_of_every_cell_is_the_dense_latent_decode_bit_for_bit():
    rng = np.random.default_rng(3)
    c_pages, r_pages = _pools(rng)
    tables = jnp.asarray(rng.permutation(8).reshape(2, 4) + 1, jnp.int32)
    q_abs = jnp.asarray(rng.normal(size=(2, 1, 4, 32)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(2, 1, 4, 8)), jnp.float32)
    kv_lens = jnp.asarray([13, 30], jnp.int32)
    dense = attention.paged_latent_decode(
        q_abs, q_rope, c_pages, r_pages[..., :128], 0, tables, kv_lens,
        scale=0.2)
    sparse = attention.paged_latent_decode(
        q_abs, q_rope, c_pages, r_pages, 0, tables, kv_lens, scale=0.2,
        selected=jnp.ones((2, 1, 32), bool))
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sparse))


def test_the_blocked_extend_is_the_masked_attention_of_the_whole_context(
        monkeypatch):
    """paged_latent_extend under a selection, three blocks of two pages and
    a table that is no multiple of the block, against the plain masked
    softmax over the gathered context."""
    monkeypatch.setattr(attention, "EXTEND_KEY_PAGES", 2)
    rng = np.random.default_rng(4)
    c_pages, r_pages = _pools(rng, pages=11)
    tables = jnp.asarray(rng.permutation(10).reshape(2, 5) + 1, jnp.int32)
    t = 6
    q_abs = jnp.asarray(rng.normal(size=(2, t, 4, 32)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(2, t, 4, 8)), jnp.float32)
    positions = jnp.asarray([[20 + i for i in range(t)],
                             [3 + i for i in range(t)]], jnp.int32)
    cells = jnp.arange(40)
    seen = cells[None, None, :] <= positions[:, :, None]
    chosen = seen & jnp.asarray(rng.random((2, t, 40)) < 0.5)
    chosen = chosen.at[:, :, 0].set(True)
    got = attention.paged_latent_extend(
        q_abs, q_rope, c_pages, r_pages, 1, tables, positions, scale=0.3,
        selected=chosen)
    c = attention.gather_kv_pages(c_pages, tables, layer=1)
    r = attention.gather_kv_pages(r_pages, tables, layer=1)[..., :128]
    want = attention._latent_attend(q_abs, q_rope, c, r, chosen, 0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_ring_decode_is_the_latent_kernel_under_a_name_of_its_own():
    """A ring a page: the latent decode kernel over [L, slots, R, C] with
    the table the rows' slots and the length the cells in use."""
    rng = np.random.default_rng(5)
    ring_c = jnp.asarray(rng.normal(size=(2, 4, 128, 32)), jnp.float32)
    ring_r = jnp.asarray(rng.normal(size=(2, 4, 128, 128)), jnp.float32)
    q_abs = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(3, 4, 128)), jnp.float32)
    slots = jnp.asarray([2, 0, 1], jnp.int32)
    kv_lens = jnp.asarray([5, 0, 3], jnp.int32)
    got = kernels.paged_latent_decode(
        q_abs, q_rope, ring_c, ring_r, 1, slots[:, None], kv_lens, scale=0.2,
        interpret=True, name="window_latent_decode")
    seen = (jnp.arange(128)[None, :] < kv_lens[:, None])[:, None, :]
    want = attention._latent_attend(
        q_abs[:, None], q_rope[:, None], ring_c[1, slots], ring_r[1, slots],
        seen, 0.2)[:, 0]
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], atol=1e-5)


# --- the extend kernel under a selection (interpreted) ------------------------


def _chunk(rng, starts, t, *, heads=4, table=8, ps=8, density=0.5):
    """A chunk of `t` queries a row from `starts` on over tables of `table`
    pages of `ps` cells (a row's unused entries the trash page, 0), and a
    selection of about `density` of the cells each query sees."""
    b = len(starts)
    c_pages, r_pages = _pools(rng, pages=b * table + 1, ps=ps)
    positions = jnp.asarray(np.asarray(starts)[:, None] + np.arange(t)[None],
                            jnp.int32)
    used = -(-(np.asarray(starts) + t) // ps)  # pages a row's context fills
    tables = rng.permutation(b * table).reshape(b, table) + 1
    tables[np.arange(table)[None, :] >= used[:, None]] = 0
    q_abs = jnp.asarray(rng.normal(size=(b, t, heads, 32)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, t, heads, 8)), jnp.float32)
    cells = jnp.arange(table * ps)
    seen = cells[None, None, :] <= positions[:, :, None]
    chosen = seen & jnp.asarray(rng.random(seen.shape) < density)
    chosen = chosen.at[:, :, 0].set(True)  # never an empty softmax
    return (q_abs, q_rope, c_pages, r_pages, jnp.asarray(tables, jnp.int32),
            positions, chosen)


def _extend_kernel(chunk, lens=None, layer=1, **kw):
    q_abs, q_rope, c_pages, r_pages, tables, positions, chosen = chunk
    if lens is None:
        lens = [q_abs.shape[1]] * q_abs.shape[0]
    return np.asarray(kernels.sparse_latent_extend(
        q_abs, attention._pad_last(q_rope, 128), c_pages, r_pages, layer,
        tables, positions, jnp.asarray(lens, jnp.int32), chosen, scale=0.3,
        interpret=True, **kw))


def _whole_context(chunk, layer=1):
    """The masked softmax over the gathered context, every score at once."""
    q_abs, q_rope, c_pages, r_pages, tables, _, chosen = chunk
    c = attention.gather_kv_pages(c_pages, tables, layer=layer)
    r = attention.gather_kv_pages(r_pages, tables, layer=layer)[..., :128]
    return np.asarray(attention._latent_attend(q_abs, q_rope, c, r, chosen,
                                               0.3))


@pytest.mark.parametrize("t", [16, 128, 512])
@pytest.mark.parametrize("starts", [(5,), (37, 0, 18), (64, 121)],
                         ids=["one-row", "three-rows", "a-groups-first-cell"])
def test_the_extend_kernel_is_the_blocked_einsums_and_the_whole_context(
        starts, t):
    """The interpreted kernel at the chunk buckets, one row and three of
    different contexts (ending inside a page and inside a group of pages,
    starting on a group's first cell), against the blocked einsums it
    replaces on the chip and against the masked softmax of the whole
    context at once."""
    rng = np.random.default_rng(t + len(starts))
    heads = 4 if t < 512 else 2
    table = -(-(max(starts) + t + 8) // 64) * 8  # whole groups of 8 pages
    chunk = _chunk(rng, starts, t, heads=heads, table=table)
    got = _extend_kernel(chunk)
    blocked = np.asarray(attention._latent_extend_blocked(
        *chunk[:4], 1, *chunk[4:], 0.3))
    np.testing.assert_allclose(got, blocked, atol=2e-5)
    np.testing.assert_allclose(got, _whole_context(chunk), atol=2e-5)


@pytest.mark.parametrize("block_q,group", [
    (8, 1), (8, 2), (16, 4), (4, 8), (16, 1), (2, 2), (8, 8), (16, 8)])
def test_every_block_shape_of_the_extend_kernel_gives_the_same_answer(
        block_q, group):
    rng = np.random.default_rng(block_q * group)
    chunk = _chunk(rng, (21, 2), 16)
    got = _extend_kernel(chunk, block_q=block_q, group=group)
    np.testing.assert_allclose(got, _whole_context(chunk), atol=2e-5)


def test_a_query_that_sees_fewer_than_k_cells_attends_over_all_of_them():
    """`topk_mask` at a k above the context: every seen cell is chosen, and
    the kernel under that selection is the dense latent extend."""
    rng = np.random.default_rng(11)
    q_abs, q_rope, c_pages, r_pages, tables, positions, _ = _chunk(
        rng, (9, 30), 16)
    cells = jnp.arange(64)
    seen = cells[None, None, :] <= positions[:, :, None]
    chosen = attention.topk_mask(
        jnp.asarray(rng.normal(size=seen.shape), jnp.float32), seen, 2048)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(seen))
    got = _extend_kernel((q_abs, q_rope, c_pages, r_pages, tables, positions,
                          chosen))
    dense = attention.paged_latent_extend(
        q_abs, q_rope, c_pages, r_pages[..., :128], 1, tables, positions,
        scale=0.3)
    np.testing.assert_allclose(got, np.asarray(dense), atol=2e-5)


def test_a_chunks_padding_queries_are_skipped_and_the_real_ones_unmoved():
    """Rows of 16, 11 and 3 valid queries of 16: the valid ones read as
    they do without padding, a q block wholly of padding comes back as
    zeros and costs no product (its last position is -1: no group counts)."""
    rng = np.random.default_rng(12)
    chunk = _chunk(rng, (30, 4, 17), 16)
    lens = [16, 11, 3]
    want = _whole_context(chunk)
    got = _extend_kernel(chunk, lens, block_q=8, group=2)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-5)
    assert (got[2, 8:] == 0).all()  # the block of queries 8..15 of row 2
    assert np.isfinite(got).all()


def test_the_extend_kernel_never_reads_past_a_selection_or_a_position():
    """Garbage in every cell outside the selection — the trash page's, the
    pages past a row's context, the cells a query does not choose — changes
    nothing: bit for bit."""
    rng = np.random.default_rng(13)
    chunk = _chunk(rng, (19, 3), 16)
    q_abs, q_rope, c_pages, r_pages, tables, positions, chosen = chunk
    assert (np.asarray(tables) == 0).any()  # unused entries: the trash page
    got = _extend_kernel(chunk)
    named = np.asarray(chosen).any(axis=1).reshape(2, 8, 8)  # by any query
    dirty = np.asarray(c_pages).copy()
    dirty[1, 0] = 1e4
    for b in range(2):
        for j in range(8):
            if int(tables[b, j]):
                dirty[1, int(tables[b, j])][~named[b, j]] = 1e4
    again = _extend_kernel((q_abs, q_rope, jnp.asarray(dirty), r_pages,
                            tables, positions, chosen))
    np.testing.assert_array_equal(again, got)


def test_the_extend_dispatcher_names_its_route_and_takes_whole_tiles_only(
        monkeypatch):
    """On the CPU the selection's extend is the blocked einsums ("xla");
    with the kernels asked for it is the Pallas call at heads of 16 and a
    chunk of 8, and the einsums again at shapes that are no whole tiles."""
    rng = np.random.default_rng(14)
    chunk = _chunk(rng, (9,), 8, heads=16)
    q_abs, q_rope, c_pages, r_pages, tables, positions, chosen = chunk
    args = (q_abs, q_rope, c_pages, r_pages, 1, tables, positions)
    plain = attention.paged_latent_extend(*args, scale=0.3, selected=chosen)
    assert attention.traced_routes()["sparse_latent_extend"] == "xla"
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    got = attention.paged_latent_extend(
        *args, scale=0.3, selected=chosen,
        chunk_lens=jnp.asarray([8], jnp.int32))
    assert (attention.traced_routes()["sparse_latent_extend"]
            == "pallas:sparse_latent_extend")
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), atol=2e-5)
    odd = attention.paged_latent_extend(
        q_abs[:, :6], q_rope[:, :6], *args[2:6], positions[:, :6], scale=0.3,
        selected=chosen[:, :6])
    assert attention.traced_routes()["sparse_latent_extend"] == "xla"
    np.testing.assert_allclose(np.asarray(odd), np.asarray(plain)[:, :6],
                               atol=2e-5)


def test_the_extend_kernels_blocks_are_a_function_of_the_shapes():
    assert kernels.sparse_extend_blocks(512, 136) == (16, 4)  # the cell's
    assert kernels.sparse_extend_blocks(32, 136) == (16, 4)
    assert kernels.sparse_extend_blocks(8, 34) == (8, 2)
    assert kernels.sparse_extend_blocks(512, 17) == (16, 1)
    assert list(inspect.signature(kernels.sparse_extend_blocks).parameters
                ) == ["queries", "pages"]
