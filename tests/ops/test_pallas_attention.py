"""Pallas attention kernels vs the XLA einsum baselines (interpret mode on CPU).

Mirrors the reference's unit-tier strategy (SURVEY.md §4): pure-logic numeric
checks, no hardware dependency — `interpret=True` runs the same kernel the TPU
compiles, through the Pallas interpreter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.ops.attention import (
    gather_kv_pages,
    gqa_attention_decode,
    gqa_attention_extend,
    paged_attention_decode,
    paged_attention_extend,
    gqa_attention_prefill,
)
from llmlb_tpu.ops.pallas_attention import (
    _paged_extend_call,
    decode_work_list,
    extend_body,
    flash_prefill,
    paged_flash_decode,
    paged_flash_extend,
)
from tests.ops.pools import (
    DECODE_CASES,
    DECODE_PPN,
    DECODE_PS,
    HEAD_SHAPES,
    grouped_work,
    live_pages_case,
    stacked_pool as _stacked,
)


@pytest.fixture(autouse=True)
def _pin_baseline_to_xla(monkeypatch):
    """On a 1-chip TPU host the baselines would auto-dispatch to Pallas and the
    comparisons would become pallas-vs-pallas; pin the expected path to XLA.
    (test_model_dispatch_pallas_matches_xla overrides this per-mode.)"""
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "xla")


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize(
    "b,t,h,kv,d,block_q,block_k",
    [
        (2, 64, 8, 8, 32, 32, 32),  # MHA
        (2, 64, 8, 2, 16, 16, 32),  # GQA g=4, blk_q != blk_k
        (1, 40, 4, 1, 32, 32, 32),  # MQA, ragged T
        (2, 128, 8, 4, 64, 128, 128),  # single q/k block
    ],
)
def test_flash_prefill_matches_xla(b, t, h, kv, d, block_q, block_k):
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _rand(keys[0], (b, t, h, d))
    k = _rand(keys[1], (b, t, kv, d))
    v = _rand(keys[2], (b, t, kv, d))
    prompt_lens = jax.random.randint(keys[3], (b,), 1, t + 1, jnp.int32)

    expected = gqa_attention_prefill(q, k, v, prompt_lens)
    got = flash_prefill(
        q, k, v, prompt_lens, block_q=block_q, block_k=block_k, interpret=True
    )
    # Padding rows (t >= prompt_len) are ignored downstream; compare valid rows.
    lens = np.asarray(prompt_lens)
    for bi in range(b):
        np.testing.assert_allclose(
            got[bi, : lens[bi]],
            expected[bi, : lens[bi]],
            rtol=2e-5,
            atol=2e-5,
        )


def test_flash_prefill_full_lens_all_rows():
    """With prompt_lens == T every row must match, padding included."""
    b, t, h, kv, d = 2, 48, 4, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(keys[0], (b, t, h, d))
    k = _rand(keys[1], (b, t, kv, d))
    v = _rand(keys[2], (b, t, kv, d))
    prompt_lens = jnp.full((b,), t, jnp.int32)

    expected = gqa_attention_prefill(q, k, v, prompt_lens)
    got = flash_prefill(
        q, k, v, prompt_lens, block_q=16, block_k=16, interpret=True
    )
    np.testing.assert_allclose(got, expected, rtol=2e-5, atol=2e-5)


def _paged_fixture(key, b, h, kv, d, page_size, pages_per_seq):
    """Random pool + per-row block tables drawing DISTINCT scattered pages
    (the pool is larger than needed so the gather order matters)."""
    rng = np.random.default_rng(
        int(jax.random.randint(key, (), 0, 2**31 - 1)))
    num_pages = b * pages_per_seq * 2 + 1  # page 0 reserved (trash)
    k_pages = jnp.asarray(
        rng.normal(size=(num_pages, page_size, kv, d)).astype(np.float32))
    v_pages = jnp.asarray(
        rng.normal(size=(num_pages, page_size, kv, d)).astype(np.float32))
    perm = rng.permutation(np.arange(1, num_pages))[: b * pages_per_seq]
    tables = jnp.asarray(perm.reshape(b, pages_per_seq).astype(np.int32))
    return k_pages, v_pages, tables


# bf16 against the float32 reference over the same (rounded) numbers: the
# kernel rounds the softmax's weights to the pool's dtype for the second
# product, as the XLA route does
_BF16_TOL = 2e-2


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize(
    "b,h,kv,d,page_size,pages_per_seq,dtype",
    [
        (2, 8, 8, 32, 16, 4, jnp.float32),  # MHA
        (3, 8, 2, 16, 32, 3, jnp.float32),  # GQA g=4
        (2, 4, 1, 32, 16, 2, jnp.float32),  # MQA
        # the benchmark cells' heads at the page they serve with, in the
        # pool's dtype: Mistral-7B's, Nemotron-3-Nano's, and the two ends
        (2, 32, 8, 128, 128, 2, jnp.bfloat16),  # K 8 x G 4
        (2, 32, 2, 128, 128, 2, jnp.bfloat16),  # K 2 x G 16
        (2, 8, 8, 128, 128, 2, jnp.bfloat16),  # MHA, 8 x 1
        (2, 4, 1, 128, 128, 2, jnp.bfloat16),  # MQA, 1 x 4
    ],
)
def test_paged_flash_decode_matches_dense(b, h, kv, d, page_size,
                                          pages_per_seq, dtype, layer):
    """The paged kernel gathering KV through the layer index and the block
    table must equal the einsum over the materialized (gathered) cache of
    that layer."""
    keys = jax.random.split(jax.random.PRNGKey(10), 3)
    cap = page_size * pages_per_seq
    q = _rand(keys[0], (b, 1, h, d)).astype(dtype)
    k_pages, v_pages, tables = _paged_fixture(
        keys[1], b, h, kv, d, page_size, pages_per_seq)
    k_pages, v_pages = k_pages.astype(dtype), v_pages.astype(dtype)
    kv_lens = jax.random.randint(keys[2], (b,), 1, cap + 1, jnp.int32)

    k_cache = gather_kv_pages(k_pages.astype(jnp.float32), tables)
    v_cache = gather_kv_pages(v_pages.astype(jnp.float32), tables)
    expected = gqa_attention_decode(q.astype(jnp.float32), k_cache, v_cache,
                                    kv_lens)
    got = paged_flash_decode(
        q[:, 0], _stacked(k_pages, layer), _stacked(v_pages, layer), layer,
        tables, kv_lens, interpret=True
    )
    assert got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else _BF16_TOL
    np.testing.assert_allclose(got.astype(jnp.float32), expected[:, 0],
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", sorted(HEAD_SHAPES))
def test_paged_flash_decode_lets_no_other_head_through(shape, dtype):
    """A grid step takes every query head against every KV head's columns
    of the page and masks the columns of the other heads. Nothing of them
    may reach a head's output: with every OTHER KV head's keys and values
    in the pool replaced by large finite numbers, the queries of one KV head
    come out BIT-identical."""
    kv, g = HEAD_SHAPES[shape]
    b, d, ps, ppn, layer = 2, 32, 16, 3, 1
    keys = jax.random.split(jax.random.PRNGKey(16), 2)
    q = _rand(keys[0], (b, kv * g, d)).astype(dtype)
    k_pages, v_pages, tables = _paged_fixture(keys[1], b, kv * g, kv, d, ps,
                                              ppn)
    kv_lens = jnp.array([ps * 2 + 1, ps * ppn], jnp.int32)

    def run(k, v):
        return np.asarray(paged_flash_decode(
            q, _stacked(k.astype(dtype), layer),
            _stacked(v.astype(dtype), layer), layer, tables, kv_lens,
            interpret=True).astype(jnp.float32))

    clean = run(k_pages, v_pages)
    assert np.isfinite(clean).all()
    for head in range(kv):
        others = (jnp.arange(kv) != head)[None, None, :, None]
        # alternating signs, so that a leak neither saturates nor cancels
        loud = jnp.where(jnp.arange(d) % 2 == 0, 3e4, -3e4)
        got = run(jnp.where(others, loud, k_pages),
                  jnp.where(others, -loud, v_pages))
        mine = slice(head * g, (head + 1) * g)
        np.testing.assert_array_equal(got[:, mine], clean[:, mine])


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_flash_decode_page_window(layer):
    """`pages` bounds the sweep: rows within the swept pages are exact."""
    b, h, kv, d, ps, ppn = 2, 4, 2, 16, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    q = _rand(keys[0], (b, 1, h, d))
    k_pages, v_pages, tables = _paged_fixture(keys[1], b, h, kv, d, ps, ppn)
    kv_lens = jnp.array([ps * 2, ps + 3], jnp.int32)  # within 2 pages

    k_cache = gather_kv_pages(k_pages, tables[:, :2])
    v_cache = gather_kv_pages(v_pages, tables[:, :2])
    expected = gqa_attention_decode(q, k_cache, v_cache, kv_lens)
    k_pool, v_pool = _stacked(k_pages, layer), _stacked(v_pages, layer)
    got = paged_flash_decode(
        q[:, 0], k_pool, v_pool, layer, tables, kv_lens, pages=2,
        interpret=True
    )
    np.testing.assert_allclose(got, expected[:, 0], rtol=2e-5, atol=2e-5)
    # the dispatcher derives the page count from a token window
    got2 = paged_attention_decode(
        q, k_pool, v_pool, layer, tables, kv_lens, window=2 * ps
    )
    np.testing.assert_allclose(got2, expected, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_len", ["empty", "one", "full_table",
                                    "one_in_last_page"])
def test_paged_flash_decode_extreme_lens(kv_len):
    """Row 0 at the edge of the ragged range beside an ordinary row 1: a
    single valid cell (page 0 of the row, offset 0), every cell of a full
    block table, one live cell in the row's last page (every head finds its
    one column there among the masked ones), and no cell at all — which
    must come out as finite zeros (the kernel skips every page and divides
    by a guarded l), not NaN."""
    b, h, kv, d, ps, ppn = 2, 4, 2, 16, 16, 3
    layer = 1
    keys = jax.random.split(jax.random.PRNGKey(13), 2)
    q = _rand(keys[0], (b, 1, h, d))
    k_pages, v_pages, tables = _paged_fixture(keys[1], b, h, kv, d, ps, ppn)
    n0 = {"empty": 0, "one": 1, "full_table": ps * ppn,
          "one_in_last_page": ps * (ppn - 1) + 1}[kv_len]
    kv_lens = jnp.array([n0, ps + 5], jnp.int32)

    got = paged_flash_decode(
        q[:, 0], _stacked(k_pages, layer), _stacked(v_pages, layer), layer,
        tables, kv_lens, interpret=True
    )
    k_cache = gather_kv_pages(k_pages, tables)
    v_cache = gather_kv_pages(v_pages, tables)
    expected = gqa_attention_decode(q, k_cache, v_cache, kv_lens)[:, 0]
    np.testing.assert_allclose(got[1], expected[1], rtol=2e-5, atol=2e-5)
    if n0 == 0:
        np.testing.assert_array_equal(np.asarray(got[0]), 0.0)
    else:
        np.testing.assert_allclose(got[0], expected[0], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_flash_decode_pages_below_longest_row(layer):
    """A `pages` bound BELOW the longest row's page count: rows inside the
    sweep stay exact, and the row beyond it attends over its swept pages
    only, as the XLA route's sliced table has it."""
    b, h, kv, d, ps, ppn = 3, 4, 2, 16, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(14), 2)
    q = _rand(keys[0], (b, 1, h, d))
    k_pages, v_pages, tables = _paged_fixture(keys[1], b, h, kv, d, ps, ppn)
    kv_lens = jnp.array([ps + 1, ps * ppn, 2 * ps], jnp.int32)  # row 1 beyond

    got = paged_flash_decode(
        q[:, 0], _stacked(k_pages, layer), _stacked(v_pages, layer), layer,
        tables, kv_lens, pages=2, interpret=True
    )
    k_cache = gather_kv_pages(k_pages, tables[:, :2])
    v_cache = gather_kv_pages(v_pages, tables[:, :2])
    # what the swept pages alone give: row 1 clipped to the two pages read
    swept = gqa_attention_decode(
        q, k_cache, v_cache, jnp.minimum(kv_lens, 2 * ps))[:, 0]
    np.testing.assert_allclose(got, swept, rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("group", [None, 1, 2, 3, 8])
@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_paged_flash_decode_reads_live_pages_only(case, group):
    """The kernel's contract (DECODE_CASES): live rows equal the einsum over
    their own cells, rows that are not live are exactly zero, and the trash
    page and every page no live row attends over hold NaN — so a step that
    read what it should not would show in a live row. At every group of
    pages a grid step (None: the shapes' own, 4 here): a short last
    group's missing pages are blocks in VMEM beside live ones, and hold a
    page some live row attends over."""
    kv_lens, pages = DECODE_CASES[case]
    h, kv, d, ps, layer = 4, 2, 16, DECODE_PS, 1
    rng = np.random.default_rng(15)
    tables, readable = live_pages_case(rng, kv_lens, pages)
    shape = (len(readable), ps, kv, d)
    k_pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(len(kv_lens), 1, h, d)), jnp.float32)
    lens = jnp.asarray(kv_lens, jnp.int32)

    def poisoned(pool):
        return _stacked(
            jnp.where(readable[:, None, None, None], pool, jnp.nan), layer)

    got = np.asarray(paged_flash_decode(
        q[:, 0], poisoned(k_pages), poisoned(v_pages), layer, tables, lens,
        pages=pages, interpret=True,
        work=grouped_work(group, tables, lens, ps, pages)))
    sweep = DECODE_PPN if pages is None else pages
    expected = np.asarray(gqa_attention_decode(
        q, gather_kv_pages(k_pages, tables[:, :sweep]),
        gather_kv_pages(v_pages, tables[:, :sweep]),
        jnp.minimum(lens, sweep * ps)))[:, 0]
    live = np.asarray(kv_lens) > 0
    np.testing.assert_allclose(got[live], expected[live], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(got[~live], 0.0)


def _expected_work_list(tables, kv_lens, ps, sweep):
    """(row, page, pool page) per item, by the rule in words: a live row's
    pages in order, one item for a row that is not live, rows in order; an
    item that reads nothing names the pool page of the item before it."""
    items = []
    for row, n in enumerate(kv_lens):
        for page in range(min(-(-n // ps), sweep)):
            items.append((row, page, int(tables[row, page])))
        if n == 0:
            items.append((row, 0, items[-1][2] if items
                          else int(tables[row, 0])))
    return items


@pytest.mark.parametrize("seed", range(6))
def test_decode_work_list_holds_every_live_page_once_and_in_order(seed):
    rng = np.random.default_rng(seed)
    b, ps, ppn = int(rng.integers(1, 9)), 8, int(rng.integers(1, 6))
    pages = [None, max(1, ppn - 1)][seed % 2]
    sweep = ppn if pages is None else pages
    live = rng.random(b) < 0.6
    kv_lens = np.where(live, rng.integers(1, ps * ppn + 1, b), 0)
    tables = rng.integers(0, 50, (b, ppn)).astype(np.int32)

    work = decode_work_list(jnp.asarray(tables), jnp.asarray(kv_lens),
                            page_size=ps, pages=pages)
    count = int(work.count)
    held = np.minimum(-(-kv_lens[live] // ps), sweep)
    assert count == held.sum() + (~live).sum()
    assert work.row_of.shape == (b * sweep,)  # static, whatever is live
    got = list(zip(*(np.asarray(a)[:count].tolist()
                     for a in (work.row_of, work.page_of,
                               work.pool_page_of))))
    assert got == _expected_work_list(tables, kv_lens.tolist(), ps, sweep)
    # beyond the count nothing is visited, but every index stays in range
    assert np.asarray(work.row_of).max() < b
    assert np.asarray(work.page_of).max() < sweep


def test_decode_work_list_follows_lens_inside_a_scan():
    """Under the engine's burst scan the list is rebuilt from the lengths a
    step has: a row crossing a page boundary gains an item at that step, a
    row that is not live never does."""
    ps, ppn = 8, 3
    tables = jnp.asarray(np.arange(1, 13, dtype=np.int32).reshape(4, ppn))
    live = jnp.asarray([True, False, True, True])
    lens0 = jnp.asarray([6, 17, 8, 15], jnp.int32)  # cells before step 0

    def body(lens, _):
        kv_lens = jnp.where(live, lens + 1, 0)
        work = decode_work_list(tables, kv_lens, page_size=ps)
        return lens + 1, work

    _, works = jax.jit(lambda lens: jax.lax.scan(body, lens, None, length=3)
                       )(lens0)
    for step in range(3):
        kv_lens = np.where(np.asarray(live), np.asarray(lens0) + step + 1, 0)
        count = int(works.count[step])
        got = list(zip(*(np.asarray(a)[step, :count].tolist()
                         for a in (works.row_of, works.page_of,
                                   works.pool_page_of))))
        assert got == _expected_work_list(np.asarray(tables),
                                          kv_lens.tolist(), ps, ppn)
    # 7, 8, 9 cells: one page, one page, two; 9, 10, 11: two; 16, 17, 18:
    # two, three, three; and one item for the row that is not live
    assert np.asarray(works.count).tolist() == [1 + 1 + 2 + 2, 1 + 1 + 2 + 3,
                                                2 + 1 + 2 + 3]


# Where a chunk of 8 queries lies on pages of 128 cells, a row each: from the
# middle of a page; across a page boundary inside the q block; its last query
# the ONE live cell of the row's last page; a row of no queries (padding:
# its output is not read); a chunk of one block in the row's first cells.
# With blocks of 4 the chunks start on block boundaries, as the engine's do.
_CHUNKS_OF_8 = {1: ([60, 124, 249, 17, 0], [8, 8, 8, 0, 4]),
                4: ([60, 124, 252, 16, 0], [8, 8, 8, 0, 4])}


def _chunks_of_8(b, block):
    starts, lens = _CHUNKS_OF_8[block]
    return ([starts[i % len(starts)] for i in range(b)],
            [lens[i % len(lens)] for i in range(b)])


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize(
    "b,t,h,kv,d,page_size,pages_per_seq,block_q,dtype,block",
    [
        (2, 16, 8, 8, 32, 16, 4, 16, jnp.float32, 1),  # MHA
        (2, 8, 8, 2, 16, 32, 2, 4, jnp.float32, 1),  # GQA g=4, small q blocks
        (1, 12, 4, 1, 32, 16, 3, 8, jnp.float32, 1),  # MQA, ragged T
        # the block family's call as its cell makes it (32 rows x 8 queries,
        # 4 x 8 heads of 128, pages of 128, bf16, blocks of 4), then a
        # chunk of 8 at the dense cells' heads: Mistral-7B's 8 x 4 and
        # Nemotron-3-Nano's 2 x 16 — the page as it is stored (`extend_body`)
        (32, 8, 32, 4, 128, 128, 3, 128, jnp.bfloat16, 4),
        (5, 8, 32, 8, 128, 128, 3, 128, jnp.bfloat16, 1),
        (5, 8, 32, 2, 128, 128, 3, 128, jnp.bfloat16, 1),
        # the largest q blocks that do: 2 MB of scores a grid step
        (2, 32, 32, 4, 128, 128, 2, 128, jnp.bfloat16, 4),
        (2, 64, 32, 2, 128, 128, 2, 128, jnp.bfloat16, 1),
        # the other side of the threshold at the same heads: a head at a time
        (2, 64, 32, 4, 128, 128, 2, 128, jnp.bfloat16, 4),
        (2, 64, 32, 8, 128, 128, 2, 128, jnp.bfloat16, 1),
        (2, 128, 32, 2, 128, 128, 2, 128, jnp.bfloat16, 1),
        # two q blocks a row in each form, float32
        (5, 8, 32, 4, 32, 128, 3, 4, jnp.float32, 4),
        (2, 64, 32, 8, 32, 128, 2, 32, jnp.float32, 1),
    ],
)
def test_paged_flash_extend_matches_dense(b, t, h, kv, d, page_size,
                                          pages_per_seq, block_q, dtype,
                                          block, layer):
    """The kernel reads the stacked pool at (layer, page): every other layer
    is NaN, so a page of the wrong layer shows in the output. Either form of
    its grid step (the q block's size decides, `extend_body`) against the
    float32 einsum over the gathered cache, under the causal mask and under
    the block mask."""
    keys = jax.random.split(jax.random.PRNGKey(12), 4)
    cap = page_size * pages_per_seq
    q = _rand(keys[0], (b, t, h, d)).astype(dtype)
    k_pages, v_pages, tables = _paged_fixture(
        keys[1], b, h, kv, d, page_size, pages_per_seq)
    k_pages, v_pages = k_pages.astype(dtype), v_pages.astype(dtype)
    if (t, page_size) == (8, 128):
        start_pos, chunk_lens = (jnp.asarray(x, jnp.int32)
                                 for x in _chunks_of_8(b, block))
    else:
        start_pos = jax.random.randint(keys[2], (b,), 0, (cap - t) // block,
                                       jnp.int32) * block
        chunk_lens = jax.random.randint(keys[3], (b,), 1, t + 1, jnp.int32)
    q_positions = start_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]

    k_cache = gather_kv_pages(k_pages.astype(jnp.float32), tables)
    v_cache = gather_kv_pages(v_pages.astype(jnp.float32), tables)
    expected = gqa_attention_extend(q.astype(jnp.float32), k_cache, v_cache,
                                    q_positions, block)
    k_pool, v_pool = _stacked(k_pages, layer), _stacked(v_pages, layer)
    got = paged_flash_extend(
        q, k_pool, v_pool, layer, tables, start_pos, chunk_lens,
        block_q=block_q, interpret=True, block=block,
    )
    assert got.dtype == dtype and got.shape == q.shape
    tol = 2e-5 if dtype == jnp.float32 else _BF16_TOL
    # Padding rows (t >= chunk_len) are ignored downstream; compare valid rows.
    lens = np.asarray(chunk_lens)
    for bi in range(b):
        np.testing.assert_allclose(
            got[bi, : lens[bi]].astype(jnp.float32),
            expected[bi, : lens[bi]], rtol=tol, atol=tol,
        )
    assert np.isfinite(np.asarray(got.astype(jnp.float32))).all()
    # the XLA dispatcher path must agree everywhere (it has no padding
    # skip): one gather at (layer, table), the same cells
    got2 = paged_attention_extend(
        q, k_pool, v_pool, layer, tables, q_positions, chunk_lens, block
    )
    np.testing.assert_allclose(got2.astype(jnp.float32), expected,
                               rtol=tol, atol=tol)


def test_the_extend_cases_stand_on_both_sides_of_the_threshold():
    """The cases above at 8 queries take the page as it is stored, those at
    64 (at 2 KV heads: 128) a head at a time, at the heads of the cells that
    run them."""
    for kv, past in ((4, 64), (8, 64), (2, 128)):
        assert extend_body(8, 32, kv, 128) == "page"
        assert extend_body(past, 32, kv, 128) == "heads"
        if kv != 8:
            assert extend_body(past // 2, 32, kv, 128) == "page"
    assert extend_body(4, 32, 4, 128) == "page"
    assert extend_body(32, 32, 8, 128) == "heads"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", sorted(HEAD_SHAPES))
def test_paged_flash_extend_lets_no_other_head_through(shape, dtype):
    """The extend twin of test_paged_flash_decode_lets_no_other_head_through:
    at a small q block a grid step takes every query row against every KV
    head's columns of the page and masks the other heads'. With every OTHER
    KV head's keys and values replaced by large finite numbers, the queries
    of one KV head come out BIT-identical."""
    kv, g = HEAD_SHAPES[shape]
    b, t, d, ps, ppn, layer = 2, 4, 32, 16, 3, 1
    assert extend_body(t, kv * g, kv, ps) == "page"
    keys = jax.random.split(jax.random.PRNGKey(17), 2)
    q = _rand(keys[0], (b, t, kv * g, d)).astype(dtype)
    k_pages, v_pages, tables = _paged_fixture(keys[1], b, kv * g, kv, d, ps,
                                              ppn)
    start = jnp.array([ps * 2 - 2, 5], jnp.int32)  # across a page boundary
    lens = jnp.array([t, t - 1], jnp.int32)

    def run(k, v):
        return np.asarray(paged_flash_extend(
            q, _stacked(k.astype(dtype), layer),
            _stacked(v.astype(dtype), layer), layer, tables, start, lens,
            interpret=True).astype(jnp.float32))

    clean = run(k_pages, v_pages)
    assert np.isfinite(clean).all()
    for head in range(kv):
        others = (jnp.arange(kv) != head)[None, None, :, None]
        # alternating signs, so that a leak neither saturates nor cancels
        loud = jnp.where(jnp.arange(d) % 2 == 0, 3e4, -3e4)
        got = run(jnp.where(others, loud, k_pages),
                  jnp.where(others, -loud, v_pages))
        mine = slice(head * g, (head + 1) * g)
        np.testing.assert_array_equal(got[:, :, mine], clean[:, :, mine])


@pytest.mark.parametrize("block", [1, 4])
def test_the_two_extend_bodies_agree(block):
    """One algorithm in two inner forms: at a shape both take, the masked
    product over the stored page and the product a KV head give the same
    attention (float32: to rounding's order of summation), padding rows
    and a row of no queries included."""
    b, t, kv, g, d, ps, ppn, layer = 3, 8, 4, 2, 32, 16, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(18), 2)
    q = _rand(keys[0], (b, t, kv * g, d))
    k_pages, v_pages, tables = _paged_fixture(keys[1], b, kv * g, kv, d, ps,
                                              ppn)
    start = jnp.array([ps * 2 - 4, 8, 20], jnp.int32)
    lens = jnp.array([t, 0, t - 4], jnp.int32)
    got = {body: np.asarray(_paged_extend_call(
        q, _stacked(k_pages, layer), _stacked(v_pages, layer), None, layer,
        tables, start, lens, block_q=4, interpret=True, block=block,
        body=body)) for body in ("page", "heads")}
    np.testing.assert_allclose(got["page"], got["heads"], rtol=2e-6,
                               atol=2e-6)
    assert not got["page"][1].any() and not got["heads"][1].any()


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_the_route_record_says_which_body_an_extend_program_holds(
        quantized, monkeypatch):
    """The choice is static a program, so "how often" is "in which
    programs": the dispatcher's route record (the engine's health route
    shows it under `traced`) maps each traced query count to the form of
    the kernel's grid step, and `paged_extend` keeps its values."""
    from llmlb_tpu.ops import attention

    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    monkeypatch.setattr(attention, "_traced", {})
    pool = jax.ShapeDtypeStruct((2, 5, 128, 8, 16),
                                jnp.int8 if quantized else jnp.float32)
    if quantized:
        pool = {"q": pool, "s": jax.ShapeDtypeStruct((2, 5, 128, 8),
                                                     jnp.float32)}
    for t in (8, 64):
        jax.eval_shape(
            paged_attention_extend,
            jax.ShapeDtypeStruct((1, t, 32, 16), jnp.float32), pool, pool, 0,
            jax.ShapeDtypeStruct((1, 4), jnp.int32),
            jax.ShapeDtypeStruct((1, t), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32))
    routes = attention.traced_routes()
    assert routes["paged_extend_body"] == {"8": "page", "64": "heads"}
    assert routes["paged_extend"] == (
        "pallas:paged_flash_extend" + "_quant" * quantized)
    routes["paged_extend_body"]["8"] = "?"  # a copy, not the record
    assert attention.traced_routes()["paged_extend_body"]["8"] == "page"


def test_the_route_record_says_which_group_a_decode_call_took(monkeypatch):
    """`attention.traced.paged_decode_group`: the pages a grid step of each
    traced paged decode call takes, by the call's name in a device trace —
    the shapes' own (2 at 8 KV heads of 128, 4 at 4 under a band's bound, 1
    over a ring of one page a row; 4 over the latent pools of 512 + the
    rope's tile, 3 over flat pools of 4 x 192 + 4 x 128), static a
    program."""
    from llmlb_tpu.ops import attention

    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    monkeypatch.setattr(attention, "_traced", {})

    def shapes(kv, table, d=128):
        pool = jax.ShapeDtypeStruct((2, 40, 128, kv, d), jnp.bfloat16)
        return (jax.ShapeDtypeStruct((3, 1, 32, d), jnp.bfloat16), pool, pool,
                0, jax.ShapeDtypeStruct((3, table), jnp.int32),
                jax.ShapeDtypeStruct((3,), jnp.int32))

    def decode(*operands):  # the step's own order: the list, then the call
        work = attention.paged_decode_work(*operands[1:3], *operands[4:])
        return attention.paged_attention_decode(*operands, work=work)

    def band(*operands):
        work = attention.paged_band_work(operands[1], *operands[4:])
        return attention.paged_band_decode(*operands, work=work)

    jax.eval_shape(decode, *shapes(8, 16))
    jax.eval_shape(band, *shapes(4, 17),
                   jax.ShapeDtypeStruct((3,), jnp.int32))
    def ring(q, ring_k, ring_v, layer, table, lens):  # mimo's window layers
        from llmlb_tpu.models import mimo_v2

        return mimo_v2._ring_decode(q, ring_k, ring_v, layer, table[:, 0],
                                    lens, jnp.zeros((32,), jnp.float32), {})

    jax.eval_shape(ring, *shapes(4, 1))

    def headless(heads, q_widths, k_width, v_width):
        """Operands of a decode call over pools without a head axis."""
        bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
        return (*(bf16(3, 1, heads, w) for w in q_widths),
                bf16(2, 40, 128, k_width), bf16(2, 40, 128, v_width), 0,
                jax.ShapeDtypeStruct((3, 16), jnp.int32),
                jax.ShapeDtypeStruct((3,), jnp.int32))

    def latent(*operands):  # kanana-2-30b-a3b's and longcat-flash-omni's
        work = attention.paged_decode_work(*operands[2:4], *operands[5:])
        return attention.paged_latent_decode(*operands, scale=0.1, work=work)

    def flat(*operands):  # mimo-v2-5's global layers
        from llmlb_tpu.models import mimo_v2

        cfg = mimo_v2.MimoV2Config(
            vocab_size=64, hidden_size=64, intermediate_size=64,
            num_layers=2, num_heads=64, num_kv_heads=4, head_dim=192)
        work = attention.paged_decode_work(*operands[1:3], *operands[4:])
        return mimo_v2._global_attention(cfg).decode(*operands, work=work)

    jax.eval_shape(latent, *headless(32, (512, 64), 512, 128))
    jax.eval_shape(flat, *headless(64, (192,), 4 * 192, 4 * 128))
    assert attention.traced_routes()["paged_decode_group"] == {
        "paged_flash_decode": 2, "paged_band_decode": 4,
        "paged_window_decode": 1, "paged_latent_decode": 4,
        "paged_flat_decode": 3}


def test_paged_flash_extend_under_a_scan_takes_the_layer_at_run_time():
    """The extend programs call the kernel inside the layer scan: `layer`
    is a traced scalar there, and each step must read its own layer."""
    b, t, h, kv, d, ps, ppn, layers = 2, 8, 8, 2, 16, 8, 3, 3
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand(keys[0], (b, t, h, d))
    k_pool = _rand(keys[1], (layers, b * ppn + 1, ps, kv, d))
    v_pool = _rand(keys[2], (layers, b * ppn + 1, ps, kv, d))
    tables = jnp.arange(1, b * ppn + 1, dtype=jnp.int32).reshape(b, ppn)
    start = jnp.asarray([9, 3], jnp.int32)
    lens = jnp.asarray([t, t - 3], jnp.int32)
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]

    def body(carry, layer):
        return carry, paged_flash_extend(q, k_pool, v_pool, layer, tables,
                                         start, lens, interpret=True)

    _, got = jax.jit(lambda: jax.lax.scan(
        body, 0, jnp.arange(layers, dtype=jnp.int32)))()
    for layer in range(layers):
        expected = gqa_attention_extend(
            q, gather_kv_pages(k_pool[layer], tables),
            gather_kv_pages(v_pool[layer], tables), positions)
        for bi, n in enumerate(np.asarray(lens)):
            np.testing.assert_allclose(got[layer, bi, :n], expected[bi, :n],
                                       rtol=2e-5, atol=2e-5)


def test_model_dispatch_pallas_matches_xla(monkeypatch):
    """Full model prefill+decode with LLMLB_TPU_ATTENTION=pallas vs =xla.

    Uses shapes unique to this test: the jit cache is keyed on shapes/config,
    and the dispatch env var is read at trace time.
    """
    import numpy as np

    from llmlb_tpu.models import llama
    from llmlb_tpu.models.llama import (
        LlamaConfig,
        decode_step_paged,
        init_params,
        prefill_into_pages,
    )
    from tests.support import identity_kv_pages

    cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        dtype=jnp.float32,
    )
    params = init_params(cfg, jax.random.PRNGKey(7))
    batch, seq, capacity = 3, 24, 48
    ids = jax.random.randint(jax.random.PRNGKey(8), (batch, seq), 0, 128)
    lens = jnp.array([24, 10, 17], jnp.int32)

    results = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("LLMLB_TPU_ATTENTION", mode)
        prefill_into_pages._clear_cache()
        decode_step_paged._clear_cache()
        ck, cv, tables = identity_kv_pages(llama, cfg, batch, capacity)
        logits, ck, cv = prefill_into_pages(params, cfg, ids, lens, tables,
                                            ck, cv)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        logits2, ck, cv = decode_step_paged(params, cfg, toks, lens, ck, cv,
                                            tables)
        results[mode] = (np.asarray(logits), np.asarray(logits2))

    np.testing.assert_allclose(
        results["pallas"][0], results["xla"][0], rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        results["pallas"][1], results["xla"][1], rtol=1e-4, atol=1e-4
    )
