"""Int8-paged attention parity: the quantized pool read paths (XLA gather
fallback AND Pallas interpret-mode kernels) must track the bf16 baseline
within the docs/quantization.md tolerance on unit-variance inputs."""

import numpy as np
import jax.numpy as jnp
import pytest

from llmlb_tpu.ops.attention import (
    gather_kv_pages,
    paged_attention_decode,
    paged_attention_extend,
)
from llmlb_tpu.ops.pallas_attention import (
    extend_body,
    paged_flash_decode,
    paged_flash_decode_quant,
    paged_flash_extend,
    paged_flash_extend_quant,
)
from llmlb_tpu.quant import quantize_kv
from tests.ops.pools import (
    DECODE_CASES,
    DECODE_PS,
    HEAD_SHAPES,
    grouped_work,
    live_pages_case,
    stacked_pool as _stacked,
)

B, H, K, D, P, PS, PPN = 2, 8, 4, 16, 9, 8, 4
TOL = 0.05


# (KV heads, queries a KV head, head size, page size): the module's small
# shape in float32, then pools.HEAD_SHAPES at the page and head size the
# benchmark's cells serve with, in bf16
SHAPES = {"small": (K, H // K, D, PS),
          **{name: (*heads, 128, 128) for name, heads in HEAD_SHAPES.items()}}


def _pools(seed=0, k=K, d=D, ps=PS):
    rng = np.random.default_rng(seed)
    k_pages = rng.normal(size=(P, ps, k, d)).astype(np.float32)
    v_pages = rng.normal(size=(P, ps, k, d)).astype(np.float32)
    kq, ks = quantize_kv(k_pages)
    vq, vs = quantize_kv(v_pages)
    tables = np.array([[1, 2, 3, 0], [4, 5, 6, 0]], np.int32)
    return (jnp.asarray(k_pages), jnp.asarray(v_pages),
            {"q": jnp.asarray(kq), "s": jnp.asarray(ks)},
            {"q": jnp.asarray(vq), "s": jnp.asarray(vs)},
            jnp.asarray(tables), rng)


def test_gather_kv_pages_dequantizes():
    k_pages, _, qk, _, tables, _ = _pools()
    dense = gather_kv_pages(k_pages, tables)
    deq = gather_kv_pages(qk, tables)
    assert deq.dtype == jnp.bfloat16
    assert np.abs(np.asarray(deq, np.float32)
                  - np.asarray(dense)).max() < TOL


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_decode_xla_parity(layer):
    k_pages, v_pages, qk, qv, tables, rng = _pools(1)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    kv_lens = jnp.asarray([PS * 3, PS * 2], jnp.int32)
    base = paged_attention_decode(q, _stacked(k_pages, layer),
                                  _stacked(v_pages, layer), layer, tables,
                                  kv_lens)
    quant = paged_attention_decode(q, _stacked(qk, layer),
                                   _stacked(qv, layer), layer, tables,
                                   kv_lens)
    assert np.abs(np.asarray(base) - np.asarray(quant,
                                                np.float32)).max() < TOL


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_extend_xla_parity(layer):
    k_pages, v_pages, qk, qv, tables, rng = _pools(2)
    t = 4
    q = jnp.asarray(rng.normal(size=(B, t, H, D)), jnp.float32)
    start = jnp.asarray([8, 4], jnp.int32)
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    lens = jnp.asarray([t, t - 1], jnp.int32)
    base = paged_attention_extend(q, _stacked(k_pages, layer),
                                  _stacked(v_pages, layer), layer, tables,
                                  positions, lens)
    quant = paged_attention_extend(q, _stacked(qk, layer),
                                   _stacked(qv, layer), layer, tables,
                                   positions, lens)
    assert np.abs(np.asarray(base) - np.asarray(quant,
                                                np.float32)).max() < TOL


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_flash_decode_quant_interpret_parity(layer, shape):
    """Interpret-mode kernel vs both the bf16 kernel (tolerance) and the
    XLA dequant route (the two quantized paths read identical cells)."""
    k, g, d, ps = SHAPES[shape]
    dtype = jnp.float32 if shape == "small" else jnp.bfloat16
    k_pages, v_pages, qk, qv, tables, rng = _pools(3, k, d, ps)
    qk, qv = _stacked(qk, layer), _stacked(qv, layer)
    q = jnp.asarray(rng.normal(size=(B, k * g, d)), dtype)
    # the second row's last page holds one live cell
    kv_lens = jnp.asarray([ps * 3 - 2, ps + 1], jnp.int32)
    base = paged_flash_decode(q, _stacked(k_pages, layer).astype(dtype),
                              _stacked(v_pages, layer).astype(dtype), layer,
                              tables, kv_lens, interpret=True)
    quant = paged_flash_decode_quant(
        q, qk["q"], qk["s"][layer], qv["q"], qv["s"][layer], layer, tables,
        kv_lens, interpret=True,
    )
    assert quant.dtype == dtype
    quant = np.asarray(quant, np.float32)
    assert np.abs(np.asarray(base, np.float32) - quant).max() < TOL

    # both quantized routes dequant to q.dtype before the dots, so they
    # differ only by online- vs plain-softmax accumulation order (and, in
    # bf16, by where the softmax's weights are rounded)
    xla = paged_attention_decode(q[:, None], qk, qv, layer, tables,
                                 kv_lens)[:, 0]
    assert np.abs(quant - np.asarray(xla, np.float32)).max() < (
        2e-3 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("group", [1, 2, 4, 8])
@pytest.mark.parametrize("kv", [2, 4, 8, 32])
def test_paged_flash_decode_quant_takes_a_group_of_pages_a_grid_step(
        kv, group, monkeypatch):
    """The int8 twin rides the bf16 kernel's grid step (PR 54): a group of
    a row's pages and of their scales dequantizes as one and meets the
    queries in ONE product. Against the XLA dequant route, at the cells'
    KV heads and every group: ragged rows, one not live, one alone on a
    bucket of fewer pages than a group."""
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "xla")  # the reference's route
    rng = np.random.default_rng(kv + group)
    rows, ppn, layer = 4, 5, 1
    shape = (1 + rows * ppn, PS, kv, D)
    pools = [dict(zip("qs", map(jnp.asarray, quantize_kv(
        rng.normal(size=shape).astype(np.float32))))) for _ in "kv"]
    qk, qv = (_stacked(pool, layer) for pool in pools)
    tables = jnp.asarray(1 + rng.permutation(rows * ppn).reshape(rows, ppn),
                         jnp.int32)
    q = jnp.asarray(rng.normal(size=(rows, kv * 2, D)), jnp.float32)
    for lens, pages in (([PS * 5, 0, PS * 2 + 3, 1], None),
                        ([0, 0, PS * 4 - 1, 0], 3)):
        lens = jnp.asarray(lens, jnp.int32)
        got = np.asarray(paged_flash_decode_quant(
            q, qk["q"], qk["s"][layer], qv["q"], qv["s"][layer], layer,
            tables, lens, pages=pages, interpret=True,
            work=grouped_work(group, tables, lens, PS, pages)))
        window = None if pages is None else pages * PS
        want = np.asarray(paged_attention_decode(
            q[:, None], qk, qv, layer, tables,
            lens if window is None else jnp.minimum(lens, window),
            window=window))[:, 0]
        live = np.asarray(lens) > 0
        assert np.abs(got[live] - want[live]).max() < 2e-3
        assert not got[~live].any()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_paged_flash_decode_quant_lets_no_other_head_through(shape):
    """The int8 kernel under the bf16 kernel's mask
    (test_paged_flash_decode_lets_no_other_head_through): every OTHER KV
    head's values saturated and its scales large, and the queries of one KV
    head come out BIT-identical."""
    k, g, d, ps = SHAPES[shape]
    d, ps, layer = min(d, 32), min(ps, 16), 1
    _, _, qk, qv, tables, rng = _pools(5, k, d, ps)
    q = jnp.asarray(rng.normal(size=(B, k * g, d)), jnp.float32)
    kv_lens = jnp.asarray([ps * 2 + 1, ps * 3], jnp.int32)

    def run(qk, qv):
        qk, qv = _stacked(qk, layer), _stacked(qv, layer)
        return np.asarray(paged_flash_decode_quant(
            q, qk["q"], qk["s"][layer], qv["q"], qv["s"][layer], layer,
            tables, kv_lens, interpret=True))

    clean = run(qk, qv)
    assert np.isfinite(clean).all()
    loud = jnp.where(jnp.arange(d) % 2 == 0, 127, -127).astype(jnp.int8)
    for head in range(k):
        others = jnp.arange(k) != head
        got = run(*({"q": jnp.where(others[None, None, :, None], sign * loud,
                                    pool["q"]),
                     "s": jnp.where(others[None, None, :], 1e4, pool["s"])}
                    for sign, pool in ((1, qk), (-1, qv))))
        mine = slice(head * g, (head + 1) * g)
        np.testing.assert_array_equal(got[:, mine], clean[:, mine])


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_flash_decode_quant_respects_pages_window(layer):
    """Rows within the swept pages stay exact when the sweep is bounded —
    the dequant variant must keep flash_decode's window contract."""
    _, _, qk, qv, tables, rng = _pools(4)
    qk, qv = _stacked(qk, layer), _stacked(qv, layer)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kv_lens = jnp.asarray([PS * 2, PS], jnp.int32)  # within 2 pages
    full = paged_flash_decode_quant(
        q, qk["q"], qk["s"][layer], qv["q"], qv["s"][layer], layer, tables,
        kv_lens, interpret=True,
    )
    windowed = paged_flash_decode_quant(
        q, qk["q"], qk["s"][layer], qv["q"], qv["s"][layer], layer, tables,
        kv_lens, pages=2, interpret=True,
    )
    assert np.abs(np.asarray(full)).max() < 10  # a mix of this layer's V
    np.testing.assert_allclose(np.asarray(full), np.asarray(windowed),
                               atol=1e-6)


@pytest.mark.parametrize("group", [None, 1, 2, 3, 8])
@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_paged_flash_decode_quant_reads_live_pages_only(case, group,
                                                        monkeypatch):
    """The int8 kernel under the bf16 kernel's contract (DECODE_CASES): live
    rows equal the XLA dequant route on the sound pool, rows that are not
    live are exactly zero, and the scales of the trash page and of every
    page no live row attends over are NaN — a vector dequantized from one
    of them would show in a live row. At every group of pages a grid step
    (None: the shapes' own)."""
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "xla")  # the reference's route
    kv_lens, pages = DECODE_CASES[case]
    layer = 1
    rng = np.random.default_rng(7)
    tables, readable = live_pages_case(rng, kv_lens, pages)
    shape = (len(readable), DECODE_PS, K, D)
    kq, ks = quantize_kv(rng.normal(size=shape).astype(np.float32))
    vq, vs = quantize_kv(rng.normal(size=shape).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(len(kv_lens), H, D)), jnp.float32)
    lens = jnp.asarray(kv_lens, jnp.int32)

    def poisoned(scales):
        return jnp.where(readable[:, None, None], scales, jnp.nan)

    got = np.asarray(paged_flash_decode_quant(
        q, _stacked(jnp.asarray(kq), layer), poisoned(ks),
        _stacked(jnp.asarray(vq), layer), poisoned(vs), layer, tables, lens,
        pages=pages, interpret=True,
        work=grouped_work(group, tables, lens, DECODE_PS, pages)))
    window = None if pages is None else pages * DECODE_PS
    sound = [_stacked({"q": jnp.asarray(vals), "s": jnp.asarray(scales)},
                      layer) for vals, scales in ((kq, ks), (vq, vs))]
    expected = np.asarray(paged_attention_decode(
        q[:, None], *sound, layer, tables,
        lens if window is None else jnp.minimum(lens, window),
        window=window), np.float32)[:, 0]
    live = np.asarray(kv_lens) > 0
    assert live.all() or not got[~live].any()
    if live.any():
        assert np.abs(got[live] - expected[live]).max() < 2e-3


# (shape of SHAPES, queries, block_q, block): the module's small shape at a
# chunk of 6 (its 48 rows take the page as it is stored) and split into q
# blocks of 4; then a chunk of 8 at the heads of the cells that run one —
# the block family's 4 x 8 under its block mask, Mistral-7B's and
# Nemotron-3-Nano's — and 64 queries at the same heads, a head at a time
EXTEND_SHAPES = {**SHAPES, "K4xG8": (4, 8, 128, 128)}
EXTEND_CASES = {
    "small-6": ("small", 6, 128, 1), "small-6-by-4": ("small", 6, 4, 1),
    "K4xG8-8-blocks-of-4": ("K4xG8", 8, 128, 4),
    "K8xG4-8": ("K8xG4", 8, 128, 1), "K2xG16-8": ("K2xG16", 8, 128, 1),
    "K4xG8-64-blocks-of-4": ("K4xG8", 64, 128, 4),
    "K8xG4-64": ("K8xG4", 64, 128, 1),
}


@pytest.mark.parametrize("case", sorted(EXTEND_CASES))
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_flash_extend_quant_interpret_parity(layer, case, monkeypatch):
    """The int8 kernel reads the stacked values at (layer, page) and takes
    the layer's scales; every other layer is poison (saturated values, 1e30
    scales). Against the bf16 kernel (tolerance) and against the XLA dequant
    route, which reads identical cells — in either form of the grid step
    (`extend_body`): the first row's chunk crosses a page boundary from the
    middle of a page, the second's is part padding."""
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "xla")  # the reference's route
    shape, t, block_q, block = EXTEND_CASES[case]
    k, g, d, ps = EXTEND_SHAPES[shape]
    dtype = jnp.float32 if shape == "small" else jnp.bfloat16
    k_pages, v_pages, qk, qv, tables, rng = _pools(5, k, d, ps)
    qk, qv = _stacked(qk, layer), _stacked(qv, layer)
    q = jnp.asarray(rng.normal(size=(B, t, k * g, d)), dtype)
    # three pages a row: the first chunk lies half under, half over the
    # boundary of the second and the third
    start = jnp.asarray([2 * ps - t // 2, 4], jnp.int32)
    lens = jnp.asarray([t, t - 2], jnp.int32)
    base = paged_flash_extend(q, _stacked(k_pages, layer).astype(dtype),
                              _stacked(v_pages, layer).astype(dtype), layer,
                              tables, start, lens, block_q=block_q,
                              interpret=True, block=block)
    quant = paged_flash_extend_quant(
        q, qk["q"], qk["s"][layer], qv["q"], qv["s"][layer], layer, tables,
        start, lens, block_q=block_q, interpret=True, block=block,
    )
    assert quant.dtype == dtype
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    xla = paged_attention_extend(q, qk, qv, layer, tables, positions, lens,
                                 block)
    # padding rows past chunk_lens are garbage in both — compare valid rows
    for b, n in enumerate([t, t - 2]):
        assert np.abs(np.asarray(base, np.float32)[b, :n]
                      - np.asarray(quant, np.float32)[b, :n]).max() < TOL
        assert np.abs(np.asarray(quant, np.float32)[b, :n]
                      - np.asarray(xla, np.float32)[b, :n]).max() < (
            2e-3 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_paged_flash_extend_quant_lets_no_other_head_through(shape):
    """The int8 extend kernel under the masked product's mask
    (test_paged_flash_extend_lets_no_other_head_through): every OTHER KV
    head's values saturated and its scales large, and the queries of one KV
    head come out BIT-identical."""
    k, g, d, ps = SHAPES[shape]
    d, ps, layer, t = min(d, 32), min(ps, 16), 1, 4
    assert extend_body(t, k * g, k, ps) == "page"
    _, _, qk, qv, tables, rng = _pools(5, k, d, ps)
    q = jnp.asarray(rng.normal(size=(B, t, k * g, d)), jnp.float32)
    start = jnp.asarray([ps * 2 - 2, 5], jnp.int32)  # across a page boundary
    lens = jnp.asarray([t, t - 1], jnp.int32)

    def run(qk, qv):
        qk, qv = _stacked(qk, layer), _stacked(qv, layer)
        return np.asarray(paged_flash_extend_quant(
            q, qk["q"], qk["s"][layer], qv["q"], qv["s"][layer], layer,
            tables, start, lens, interpret=True))

    clean = run(qk, qv)
    assert np.isfinite(clean).all()
    loud = jnp.where(jnp.arange(d) % 2 == 0, 127, -127).astype(jnp.int8)
    for head in range(k):
        others = jnp.arange(k) != head
        got = run(*({"q": jnp.where(others[None, None, :, None], sign * loud,
                                    pool["q"]),
                     "s": jnp.where(others[None, None, :], 1e4, pool["s"])}
                    for sign, pool in ((1, qk), (-1, qv))))
        mine = slice(head * g, (head + 1) * g)
        np.testing.assert_array_equal(got[:, :, mine], clean[:, :, mine])


@pytest.mark.parametrize("route", ["decode", "extend"])
def test_quantized_pool_means_quantized_kernel(route, monkeypatch):
    """The dispatcher must route {"q","s"} pools to the quant kernels when
    Pallas is enabled — mixing an int8 pool into the bf16 kernel would be
    garbage, not an error."""
    import llmlb_tpu.ops.attention as attn

    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    k_pages, v_pages, qk, qv, tables, rng = _pools(6)
    if route == "decode":
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
        kv_lens = jnp.asarray([PS, PS], jnp.int32)
        out = attn.paged_attention_decode(q, _stacked(qk, 1),
                                          _stacked(qv, 1), 1, tables,
                                          kv_lens)
        ref = attn.paged_attention_decode(q, _stacked(k_pages, 1),
                                          _stacked(v_pages, 1), 1, tables,
                                          kv_lens)
    else:
        q = jnp.asarray(rng.normal(size=(B, 3, H, D)), jnp.float32)
        positions = jnp.asarray([[8, 9, 10], [4, 5, 6]], jnp.int32)
        lens = jnp.asarray([3, 3], jnp.int32)
        out = attn.paged_attention_extend(q, _stacked(qk, 1),
                                          _stacked(qv, 1), 1, tables,
                                          positions, lens)
        ref = attn.paged_attention_extend(q, _stacked(k_pages, 1),
                                          _stacked(v_pages, 1), 1, tables,
                                          positions, lens)
    assert np.abs(np.asarray(out, np.float32)
                  - np.asarray(ref, np.float32)).max() < TOL
