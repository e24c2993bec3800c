"""Grouped-query attention for serving: batched prefill + single-token decode.

Design notes (TPU-first):
- Static shapes everywhere: prefill runs at bucketed sequence lengths, decode at
  T=1 over a fixed-width block table into the KV page pool. Ragged reality is
  expressed with masks, not dynamic shapes, so XLA tiles everything onto the
  MXU.
- Softmax in float32; QK^T and PV in bf16 inputs with fp32 accumulation
  (`preferred_element_type`) — the MXU accumulates in fp32 natively.
- GQA is expressed by folding the group dimension into einsum so no materialized
  `repeat_kv` copy hits HBM.

The reference gateway never touches attention (it proxies; SURVEY.md §5
"long-context: absent") — this op family is new TPU-native design. The Pallas
paged kernels (ops/pallas_attention.py, PAPERS.md) serve decode and extend on an
unpartitioned TPU; `gqa_attention_decode` / `gqa_attention_extend` are the plain
einsums they are checked against and the paged XLA fall-back ends in.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

_NEG_INF = -1e30  # large finite value: -inf breaks softmax rows that are fully masked


def _pallas_enabled() -> bool:
    """Route to the Pallas kernels (ops/pallas_attention.py)?

    `LLMLB_TPU_ATTENTION=pallas|xla` forces a path; `auto` (default) picks
    Pallas on an unpartitioned TPU. A pallas_call is opaque to XLA sharding
    propagation, so multi-device meshes keep the einsum path unless the caller
    wraps the step in shard_map and forces `pallas`.
    """
    mode = os.environ.get("LLMLB_TPU_ATTENTION", "auto")
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    return jax.default_backend() == "tpu" and jax.device_count() == 1


# What each dispatcher below resolved to the last time it was traced, by op
# ("paged_decode" -> "pallas:paged_flash_decode" or "xla"). The engine's
# /api/health serves it, so "which kernel ran" is read off the dispatch
# itself instead of a rule restated elsewhere. Three keys map further:
# "mixture" -> {rows of a traced mixture: "pallas:rows_in_place" |
# "pallas:sorted" | "xla"} (ops/moe.moe_routed: static by shape);
# "paged_extend_body" -> {queries of a traced chunk: "page" | "heads"}, the
# form of the Pallas extend kernels' grid step each extend program holds
# (pallas_attention.extend_body: static a program, so "how often" is "in
# which programs"); "paged_decode_group" -> {a paged decode call's name in a
# device trace: the pages its grid step takes} (pallas_attention.
# decode_group: static by shape, so with the step records' live pages it
# says how many grid steps a step ran).
_traced: dict[str, str | dict[str, str | int]] = {}


def note_decode_group(name: str, work) -> None:
    """Record under "paged_decode_group" the pages a grid step of the paged
    decode call `name` takes: its work-list's own group."""
    _traced.setdefault("paged_decode_group", {})[name] = work.group


def attention_mode() -> str:
    """"pallas" or "xla": the path the dispatchers take in this process."""
    return "pallas" if _pallas_enabled() else "xla"


def traced_routes() -> dict[str, str | dict[str, str | int]]:
    """op -> kernel for every attention dispatcher traced so far."""
    return {op: dict(route) if isinstance(route, dict) else route
            for op, route in _traced.items()}


def _split_gqa(q: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """[B, T, H, D] -> [B, T, K, G, D] where H = K * G."""
    b, t, h, d = q.shape
    return q.reshape(b, t, num_kv_heads, h // num_kv_heads, d)


# --- heads narrower than the chip's 128 lanes ------------------------------
# A page pool [L, P, PS, K, D] with D = 64 is stored 128 lanes wide, half of
# every tile padding: twice the bytes held and twice the bytes a paged kernel
# reads, and at a whole model's size enough for the compiler to re-lay the
# pool out inside every decode step (PERF.md section 6, PR 55). The same
# bytes as [L, P, PS, K / f, f * D] have no padding: `f` KV heads side by
# side in a row. The paged ops take such a pool as it is — K / f KV heads of
# f * D — given queries that are zero outside their own KV head's lanes.

LANES = 128


def lane_pack(num_kv: int, head_dim: int) -> int:
    """How many KV heads share a row of a lane-packed pool: as many as fill
    the 128 lanes, a power of two that divides the KV heads; 1 where the
    head is 128 wide or wider."""
    f = 1
    while 2 * f * head_dim <= LANES and num_kv % (2 * f) == 0:
        f *= 2
    return f


def pack_kv(x: jnp.ndarray, f: int) -> jnp.ndarray:
    """Keys or values [..., K, D] as a packed pool holds them,
    [..., K / f, f * D]: the same numbers in the same order."""
    *lead, k, d = x.shape
    return x.reshape(*lead, k // f, f * d)


def _own_lanes(heads: int, num_kv: int, f: int) -> jnp.ndarray:
    """[H, f] bool: the place of query head j's KV head in its packed row."""
    place = (jnp.arange(heads) // (heads // num_kv)) % f
    return place[:, None] == jnp.arange(f)[None, :]


def pack_queries(q: jnp.ndarray, num_kv: int, f: int) -> jnp.ndarray:
    """Queries [B, T, H, D] against a pool packed by `f`: [B, T, H, f * D],
    a head's vector in its own KV head's lanes and zero in the others', so
    that its product with a packed row is its product with its own key;
    times sqrt(f), because the ops scale by (f * D)^-0.5."""
    b, t, h, d = q.shape
    own = _own_lanes(h, num_kv, f)[:, :, None]
    packed = jnp.where(own, (q * f**0.5)[:, :, :, None, :], 0)
    return packed.reshape(b, t, h, f * d).astype(q.dtype)


def unpack_heads(out: jnp.ndarray, num_kv: int, f: int) -> jnp.ndarray:
    """What attention over packed values gives [B, T, H, f * D], cut to each
    head's own KV head's lanes: [B, T, H, D]."""
    b, t, h, fd = out.shape
    own = _own_lanes(h, num_kv, f)[:, :, None]
    return jnp.sum(jnp.where(own, out.reshape(b, t, h, f, fd // f), 0),
                   axis=3).astype(out.dtype)


def gqa_attention_prefill(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, T, K, D]
    v: jnp.ndarray,  # [B, T, K, D]
    prompt_lens: jnp.ndarray,  # [B] int32 — tokens beyond this are padding
    block: int = 1,  # static: the mask's block length (_block_end)
) -> jnp.ndarray:
    """Causal self-attention over a freshly-prefilled prompt, block-causal
    with `block` > 1. Returns [B, T, H, D]."""
    if _pallas_enabled():
        from llmlb_tpu.ops.pallas_attention import flash_prefill

        _traced["prefill"] = "pallas:flash_prefill"
        return flash_prefill(q, k, v, prompt_lens, block=block)
    _traced["prefill"] = "xla"
    return _prefill_einsum(q, k, v, prompt_lens, block)


def _block_end(pos, block: int):
    """The last position a query at `pos` may see. `block` (STATIC) is the
    length of the blocks generation by diffusion works in: position j is
    visible to i iff j // block <= i // block — causal across blocks,
    bidirectional inside one. At 1 that is the causal mask, and the value
    is `pos` itself: the programs of every autoregressive family are the
    ones they were."""
    return pos if block == 1 else pos - pos % block + (block - 1)


def _prefill_einsum(q, k, v, prompt_lens, block: int = 1):
    """gqa_attention_prefill as plain einsums; the values may be narrower
    than the keys. Returns [B, T, H, Dv]."""
    b, t, h, d = q.shape
    k_heads = k.shape[2]
    qg = _split_gqa(q, k_heads)
    scale = d**-0.5

    # [B, K, G, Tq, Tk] fp32 scores
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
    ) * scale

    pos = jnp.arange(t, dtype=jnp.int32)
    causal = _block_end(pos, block)[:, None] >= pos[None, :]  # [Tq, Tk]
    valid = pos[None, :] < prompt_lens[:, None, None]  # broadcast to [B, 1, Tk]
    mask = causal[None, :, :] & valid  # [B, Tq, Tk]
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bskd->btkgd", probs.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, h, v.shape[-1]).astype(q.dtype)


def gqa_attention_extend(
    q: jnp.ndarray,  # [B, T, H, D] — chunk of queries
    k_cache: jnp.ndarray,  # [B, S, K, D] — contiguous rows incl. the chunk's
    v_cache: jnp.ndarray,  # [B, S, K, D]
    q_positions: jnp.ndarray,  # [B, T] int32 — global position of each query
    block: int = 1,  # static: the mask's block length (_block_end)
) -> jnp.ndarray:
    """Chunked-prefill attention, plain einsum: a chunk of T queries attends
    causally against contiguous per-row KV (earlier chunks + this chunk).
    Query i at global position p may see positions <= p, or with `block`
    > 1 up to the end of p's block. Returns
    [B, T, H, D]. Generalizes decode (T=1). The reference the paged extend
    kernels are checked against, and what the paged XLA fall-back
    (paged_attention_extend) ends in after gathering its pages."""
    b, t, h, d = q.shape
    k_heads = k_cache.shape[2]
    qg = _split_gqa(q, k_heads)  # [B, T, K, G, D]
    scale = d**-0.5

    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale  # [B, K, G, T, S]

    s = k_cache.shape[1]
    cap_pos = jnp.arange(s, dtype=jnp.int32)
    mask = (cap_pos[None, None, :]
            <= _block_end(q_positions, block)[:, :, None])  # [B, T, S]
    scores = jnp.where(mask[:, None, None, :, :], scores, _NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bskd->btkgd", probs.astype(q.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, h, d).astype(q.dtype)


def gather_kv_pages(pages, tables: jnp.ndarray, dtype=jnp.bfloat16,
                    layer=None) -> jnp.ndarray:
    """Materialize contiguous per-row KV from the page pool: one layer's
    pages of the stacked pool [L, P, PS, K, D], gathered at (layer, block
    tables [B, N]) -> [B, N*PS, K, D] in one gather, so no layer of the
    pool is sliced out on the way; with `layer` left out, `pages` is one
    layer [P, PS, K, D]. A latent pool [L, P, PS, C] gathers the same way
    to [B, N*PS, C]. This is the XLA fallback path (CPU tests /
    partitioned meshes) — on an unpartitioned TPU the Pallas paged kernels
    index the pool through the block table instead and never build this
    copy.

    An int8 pool arrives as a {"q": int8 values, "s": f32 scales [.., P, PS,
    K]} pair (llmlb_tpu/quant): both gather through the same table and the
    cells dequantize to `dtype` here — the attention callers pass their
    compute dtype so this route matches the Pallas quant kernels' numerics
    exactly (f32 dequant -> q.dtype operands). HBM moved the int8 bytes +
    scales."""
    at = tables if layer is None else (layer, tables)
    b, n = tables.shape

    def rows(x):  # [B, N, PS, ...] -> [B, N*PS, ...]
        return x.reshape(b, n * x.shape[2], *x.shape[3:])

    if isinstance(pages, dict):
        return (rows(pages["q"][at]).astype(jnp.float32)
                * rows(pages["s"][at])[..., None]).astype(dtype)
    return rows(pages[at])


def _pool_shape(pages):
    return (pages["q"] if isinstance(pages, dict) else pages).shape


def _window_pages(block_tables, page_size: int, window: int | None) -> int:
    """Whole pages covering `window` cells, within the table's width.
    `window` here and in every `window=` below is the engine's CONTEXT
    BUCKET (a row's FIRST `window` cells), not a model's sliding window:
    that is a lower bound a row, `kv_from` (paged_band_decode)."""
    ppn = block_tables.shape[1]
    return ppn if window is None else max(1, min(ppn, -(-window // page_size)))


def paged_decode_work(
    k_pages,  # the two pools the step's decode calls will be handed: keys
    v_pages,  # and values, the latent and the rope's tile, with or without
    # a head axis
    block_tables: jnp.ndarray,  # [B, PPN] int32
    kv_lens: jnp.ndarray,  # [B] int32 — valid length per row; 0 = not live
    window: int | None = None,
):
    """What a decode step builds ONCE and hands every layer's paged decode
    call (paged_attention_decode, paged_latent_decode, a flat pool's) as
    `work`: on the Pallas route the kernels' grid, the work-list of the live
    rows' pages, a group of a row's pages an item
    (pallas_attention.decode_work_list; decode_group of what a page holds in
    both pools as they are stored); on the XLA route nothing."""
    if not _pallas_enabled():
        return None
    from llmlb_tpu.ops.pallas_attention import decode_group, decode_work_list

    shapes = _pool_shape(k_pages), _pool_shape(v_pages)
    ps = shapes[0][2]
    pages = _window_pages(block_tables, ps, window)
    page_elements = sum(math.prod(shape[2:]) for shape in shapes)
    return decode_work_list(block_tables, kv_lens, page_size=ps, pages=pages,
                            group=decode_group(page_elements, pages))


def paged_attention_decode(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_pages,  # [L, P, PS, K, D] stacked pool, or quantized {"q","s"} pair
    v_pages,  # [L, P, PS, K, D]
    layer,  # int32 scalar — the layer of the pool to attend over
    block_tables: jnp.ndarray,  # [B, PPN] int32
    kv_lens: jnp.ndarray,  # [B] int32 — valid logical length; 0 = not live
    window: int | None = None,  # static: read only the first `window` cells
    work=None,  # paged_decode_work of the same tables, lengths and window
) -> jnp.ndarray:
    """One-token decode attention against one layer of the KV page pool.
    `window` (STATIC, the context bucket; a sliding window is `kv_from` of
    paged_band_decode) bounds what a row attends over, rounded up to whole
    pages: a row attends over its first min(kv_lens, window) cells. A row
    with kv_lens 0 is not live (the engine's freed, never-used and
    prefilling slot rows): the Pallas kernels write it as zeros and read no
    page for it, the XLA fall-back gives it the mean of its window's cells —
    finite either way, and the caller discards it. The Pallas kernels' cost
    follows the live pages (their grid is `work`, built once a step by the
    caller, or here when it is left out); the XLA fall-back gathers the
    window, so there the scheduler's smallest bucket covering every active
    sequence still bounds the traffic.

    The pool arrives STACKED over layers, with the layer index beside it:
    the Pallas kernels address it at (layer, page) and read it in place,
    where a `pool[layer]` operand would be copied whole on every call (an
    int8 pool's scales are the exception, and the smaller part: see
    paged_flash_decode_quant). The XLA fallback (CPU tests, partitioned
    meshes) gathers the window's pages at (layer, table)."""
    ps = _pool_shape(k_pages)[2]
    ppn = block_tables.shape[1]
    pages = _window_pages(block_tables, ps, window)
    if _pallas_enabled():
        if work is None:
            work = paged_decode_work(k_pages, v_pages, block_tables, kv_lens,
                                     window)
        if isinstance(k_pages, dict):
            from llmlb_tpu.ops.pallas_attention import paged_flash_decode_quant

            _traced["paged_decode"] = "pallas:paged_flash_decode_quant"
            note_decode_group("paged_flash_decode_quant", work)
            return paged_flash_decode_quant(
                q[:, 0], k_pages["q"], k_pages["s"][layer], v_pages["q"],
                v_pages["s"][layer], layer, block_tables, kv_lens,
                pages=pages, work=work,
            )[:, None]
        from llmlb_tpu.ops.pallas_attention import paged_flash_decode

        _traced["paged_decode"] = "pallas:paged_flash_decode"
        note_decode_group("paged_flash_decode", work)
        return paged_flash_decode(
            q[:, 0], k_pages, v_pages, layer, block_tables, kv_lens,
            pages=pages, work=work,
        )[:, None]
    _traced["paged_decode"] = "xla"
    tables = block_tables[:, :pages] if pages < ppn else block_tables
    k_cache = gather_kv_pages(k_pages, tables, dtype=q.dtype, layer=layer)
    v_cache = gather_kv_pages(v_pages, tables, dtype=q.dtype, layer=layer)
    return gqa_attention_decode(q, k_cache, v_cache, kv_lens)


def band_positions(kv_lens: jnp.ndarray, cells: int) -> jnp.ndarray:
    """The position each cell of a band of `cells` cells a row holds once
    the row is `kv_lens` [B] long — position p lives in cell p mod `cells`,
    so cell c holds the largest p < len with p mod cells == c — and below 0
    where there is none yet. [B, cells]."""
    cell = jnp.arange(cells, dtype=jnp.int32)[None, :]
    last = kv_lens[:, None] - 1 - cell  # >= 0 where the cell has been written
    return jnp.where(last >= 0, cell + last // cells * cells, -1)


def paged_band_work(k_pages, band_tables: jnp.ndarray, kv_lens: jnp.ndarray,
                    kv_from: jnp.ndarray):
    """paged_decode_work for paged_band_decode: built once a step for all
    the layers that share the band's tables; nothing on the XLA route."""
    if not _pallas_enabled():
        return None
    from llmlb_tpu.ops.pallas_attention import decode_group, decode_work_list

    _, _, ps, num_kv, d = _pool_shape(k_pages)
    return decode_work_list(
        band_tables, kv_lens, page_size=ps, kv_from=kv_from,
        group=decode_group(ps * num_kv * 2 * d, band_tables.shape[1]))


BAND_DECODE = "paged_band_decode"  # the call's name in a device trace


def paged_band_decode(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_pages: jnp.ndarray,  # [L, P, PS, K, D] — the bands' pages, stacked
    v_pages: jnp.ndarray,  # [L, P, PS, K, D]
    layer,  # int32 scalar
    band_tables: jnp.ndarray,  # [B, R] int32 — the row's own R pages
    kv_lens: jnp.ndarray,  # [B] int32 — the row's length; 0 = not live
    kv_from: jnp.ndarray,  # [B] int32 — the first position the row reads
    work=None,  # paged_band_work of the same operands
) -> jnp.ndarray:
    """One-token decode attention under a SLIDING WINDOW held as a band of
    R pages a row: position p lives in column (p // PS) mod R of the row's
    table, cell p mod PS, so the band always holds the last (R - 1) x PS
    positions whole. A row attends over positions `kv_from <= p < kv_lens`
    (the caller keeps that span within the band: kv_lens - kv_from <= (R -
    1) x PS). `kv_from` is the model's bound and a run-time value a row;
    the static `window=` of paged_attention_decode is the context bucket
    and another thing. On the Pallas route this is ONE call of
    paged_flash_decode whose work-list holds only the pages of the span,
    masked at both ends; the XLA fall-back gathers the R pages and masks by
    the position each cell holds."""
    if _pallas_enabled():
        from llmlb_tpu.ops.pallas_attention import paged_flash_decode

        if work is None:
            work = paged_band_work(k_pages, band_tables, kv_lens, kv_from)
        _traced["band_decode"] = "pallas:" + BAND_DECODE
        note_decode_group(BAND_DECODE, work)
        return paged_flash_decode(
            q[:, 0], k_pages, v_pages, layer, band_tables, kv_lens,
            work=work, kv_from=kv_from, name=BAND_DECODE)[:, None]
    _traced["band_decode"] = "xla"
    k_cache = gather_kv_pages(k_pages, band_tables, dtype=q.dtype, layer=layer)
    v_cache = gather_kv_pages(v_pages, band_tables, dtype=q.dtype, layer=layer)
    held = band_positions(kv_lens, k_cache.shape[1])
    return gqa_attention_decode(q, k_cache, v_cache, kv_lens,
                                valid=held >= kv_from[:, None])


def paged_attention_extend(
    q: jnp.ndarray,  # [B, T, H, D] — chunk of queries
    k_pages,  # [L, P, PS, K, D] stacked pool, or quantized {"q","s"} pair
    v_pages,  # [L, P, PS, K, D]
    layer,  # int32 scalar — the layer of the pool to attend over
    block_tables: jnp.ndarray,  # [B, PPN] int32
    q_positions: jnp.ndarray,  # [B, T] int32 — global position of each query
    chunk_lens: jnp.ndarray,  # [B] int32 — valid queries in the chunk
    block: int = 1,  # static: the mask's block length (_block_end)
) -> jnp.ndarray:
    """Chunked-prefill attention against one layer of the KV page pool: the
    chunk's queries attend causally (block-causally with `block` > 1, the
    chunk then starting and ending on block boundaries) over row b's pages
    (earlier chunks + this chunk). Assumes the engine's contiguous chunk
    positions
    (q_positions[b] = start + iota). The pool arrives stacked with the layer
    index beside it, as paged_attention_decode's does and for its reason:
    under the extend program's layer scan `layer` is a run-time value and
    the pool the scan's carry."""
    if _pallas_enabled():
        from llmlb_tpu.ops.pallas_attention import EXTEND_BLOCK_Q, extend_body

        _, t, heads, _ = q.shape
        _, _, ps, num_kv, _ = _pool_shape(k_pages)
        _traced.setdefault("paged_extend_body", {})[str(t)] = extend_body(
            min(EXTEND_BLOCK_Q, t), heads, num_kv, ps)
        if isinstance(k_pages, dict):
            from llmlb_tpu.ops.pallas_attention import paged_flash_extend_quant

            _traced["paged_extend"] = "pallas:paged_flash_extend_quant"
            return paged_flash_extend_quant(
                q, k_pages["q"], k_pages["s"][layer], v_pages["q"],
                v_pages["s"][layer], layer, block_tables, q_positions[:, 0],
                chunk_lens, block=block,
            )
        from llmlb_tpu.ops.pallas_attention import paged_flash_extend

        _traced["paged_extend"] = "pallas:paged_flash_extend"
        return paged_flash_extend(
            q, k_pages, v_pages, layer, block_tables, q_positions[:, 0],
            chunk_lens, block=block,
        )
    _traced["paged_extend"] = "xla"
    k_cache = gather_kv_pages(k_pages, block_tables, dtype=q.dtype,
                              layer=layer)
    v_cache = gather_kv_pages(v_pages, block_tables, dtype=q.dtype,
                              layer=layer)
    return gqa_attention_extend(q, k_cache, v_cache, q_positions, block)


def gqa_attention_decode(
    q: jnp.ndarray,  # [B, 1, H, D]
    k_cache: jnp.ndarray,  # [B, S, K, D] — contiguous rows incl. current token
    v_cache: jnp.ndarray,  # [B, S, K, D]
    kv_lens: jnp.ndarray,  # [B] int32 — valid length per row (incl. current)
    valid: jnp.ndarray | None = None,  # [B, S] bool — the cells seen instead
) -> jnp.ndarray:
    """One-token decode attention against contiguous per-row KV, plain
    einsum. Returns [B, 1, H, D]. The reference the paged decode kernels are
    checked against, and what the paged XLA fall-back
    (paged_attention_decode) ends in after gathering its pages. `valid`,
    where given, says cell by cell what a row sees (a band's cells are not
    in the positions' order: paged_band_decode)."""
    s = k_cache.shape[1]
    b, t, h, d = q.shape
    k_heads = k_cache.shape[2]
    qg = _split_gqa(q, k_heads)  # [B, 1, K, G, D]
    scale = d**-0.5

    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale  # [B, K, G, 1, S]

    if valid is None:
        valid = jnp.arange(s, dtype=jnp.int32)[None, :] < kv_lens[:, None]
    scores = jnp.where(valid[:, None, None, None, :], scores, _NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bskd->btkgd", probs.astype(q.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent attention (MLA, models/deepseek_v3.py): a token leaves in the pool
# its normalised latent `c` [C] and the rotated key `k_rope` [R] that all
# heads share — two pools [L, P, PS, C] and [L, P, PS, R] under the same
# page ids, no head axis. Prefill materialises keys and values from the
# chunk's own latent; extend, verify and decode attend in the ABSORBED form:
# the query is carried into the latent space (q_abs = W^K_h q_nope, [H, C]),
# scores are q_abs . c + q_rope . k_rope, and the output is the softmax's
# mix of the latents themselves, which the caller carries back out through
# W^V_h. The latent is key and value at once, read once.
# ---------------------------------------------------------------------------


def latent_attention_prefill(
    q: jnp.ndarray,  # [B, T, H, Dq] — [q_nope | q_rope]
    k: jnp.ndarray,  # [B, T, H, Dq] — [k_nope | k_rope], materialised
    v: jnp.ndarray,  # [B, T, H, Dv], Dv <= Dq
    prompt_lens: jnp.ndarray,  # [B] int32
) -> jnp.ndarray:
    """Causal self-attention over a fresh prompt with every head its own
    keys (no grouping) and values narrower than keys: plain einsums on every
    backend. `flash_prefill` is written for grouped heads of one width, and
    Mosaic refuses it at 32 ungrouped heads of 192 ("unsupported shape
    cast", my chip run, PR 31); a prefill kernel for this shape is ROADMAP
    work. Scale Dq ** -0.5. Returns [B, T, H, Dv]."""
    _traced["latent_prefill"] = "xla"
    return _prefill_einsum(q, k, v, prompt_lens)


def _pad_last(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """x with zeros appended on its last axis up to `width`: the rope pool
    is a whole 128-lane tile wide (pallas_attention.paged_latent_decode),
    the rope part of a query 64."""
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _latent_attend(q_abs, q_rope, c, r, mask, scale):
    """q_abs [B, T, H, C], q_rope [B, T, H, R'] against c [B, S, C] and
    r [B, S, R] (R' <= R, the rest of r zeros); mask [B, T, S]. Returns the
    mix of latents [B, T, H, C]."""
    q_rope = _pad_last(q_rope, r.shape[-1])
    scores = (
        jnp.einsum("bthc,bsc->bhts", q_abs, c,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bthr,bsr->bhts", q_rope, r,
                     preferred_element_type=jnp.float32)
    ) * scale
    scores = jnp.where(mask[:, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bsc->bthc", probs.astype(c.dtype), c,
                     preferred_element_type=jnp.float32)
    return out.astype(q_abs.dtype)


def paged_latent_extend(
    q_abs: jnp.ndarray,  # [B, T, H, C]
    q_rope: jnp.ndarray,  # [B, T, H, R]
    c_pages: jnp.ndarray,  # [L, P, PS, C] stacked latent pool
    r_pages: jnp.ndarray,  # [L, P, PS, R]
    layer,  # int32 scalar
    block_tables: jnp.ndarray,  # [B, PPN] int32
    q_positions: jnp.ndarray,  # [B, T] int32 — global position of each query
    *,
    scale: float,
    selected: jnp.ndarray | None = None,  # [B, T, S] bool: topk_mask's
    chunk_lens: jnp.ndarray | None = None,  # [B] int32 — valid queries
) -> jnp.ndarray:
    """Absorbed attention of a chunk of queries over row b's pages (earlier
    chunks, a cached prefix, this chunk) of one layer, causal by position.
    Returns the mix of latents [B, T, H, C]. Without a selection: plain
    einsums over the latent gathered at (layer, table) on every backend (a
    chunk's work is the experts', not this). Under `selected` a query's
    softmax runs over the cells it names alone — contexts too long to hold
    queries x heads x context scores at once: on an unpartitioned TPU ONE
    Pallas kernel with a block's scores in VMEM
    (`pallas_attention.sparse_latent_extend`: the pools read in place, key
    groups past a q block's last position and q blocks past `chunk_lens`
    skipped, so a padding query's row may come back as zeros), where its
    shapes are whole tiles (heads a multiple of 16, queries of 8); elsewhere
    — the CPU, a partitioned mesh, the tests' plain reference — a BLOCK of
    pages at a time in einsums with an online softmax
    (`_latent_extend_blocked`: padding queries attend like real ones)."""
    if selected is not None:
        _, t, h, _ = q_abs.shape
        if _pallas_enabled() and h % 16 == 0 and t % 8 == 0:
            from llmlb_tpu.ops import pallas_attention as kernels

            _traced["sparse_latent_extend"] = "pallas:" + kernels.SPARSE_EXTEND
            if chunk_lens is None:
                chunk_lens = jnp.full(q_abs.shape[:1], t, jnp.int32)
            return kernels.sparse_latent_extend(
                q_abs, _pad_last(q_rope, LANES), c_pages, r_pages, layer,
                block_tables, q_positions, chunk_lens, selected, scale=scale)
        _traced["sparse_latent_extend"] = "xla"
        return _latent_extend_blocked(q_abs, q_rope, c_pages, r_pages, layer,
                                      block_tables, q_positions, selected,
                                      scale)
    _traced["latent_extend"] = "xla"
    c = gather_kv_pages(c_pages, block_tables, layer=layer)  # [B, S, C]
    r = gather_kv_pages(r_pages, block_tables, layer=layer)
    cell = jnp.arange(c.shape[1], dtype=jnp.int32)
    mask = cell[None, None, :] <= q_positions[:, :, None]
    return _latent_attend(q_abs, q_rope, c, r, mask, scale)


def paged_latent_decode(
    q_abs: jnp.ndarray,  # [B, 1, H, C]
    q_rope: jnp.ndarray,  # [B, 1, H, R]
    c_pages: jnp.ndarray,  # [L, P, PS, C] stacked latent pool
    r_pages: jnp.ndarray,  # [L, P, PS, R]
    layer,  # int32 scalar
    block_tables: jnp.ndarray,  # [B, PPN] int32
    kv_lens: jnp.ndarray,  # [B] int32 — valid length; 0 = not live
    *,
    scale: float,
    window: int | None = None,
    work=None,  # paged_decode_work of the same tables, lengths and window
    selected: jnp.ndarray | None = None,  # [B, 1, S] bool: topk_mask's
) -> jnp.ndarray:
    """One-token absorbed attention against one layer of the latent pool:
    same contract as paged_attention_decode (`window`, rows that are not
    live, the stacked pool addressed at (layer, page), `work`). Returns the
    mix of latents [B, 1, H, C]. Under `selected` (over the cells of the
    swept pages) a row's softmax runs over the cells it names alone; the
    rope pool's row may then be wider than the rope's tile (an index key
    behind it), and its first 128 lanes are read."""
    ps = c_pages.shape[2]
    ppn = block_tables.shape[1]
    pages = _window_pages(block_tables, ps, window)
    if _pallas_enabled():
        from llmlb_tpu.ops import pallas_attention as kernels

        if work is None:
            work = paged_decode_work(c_pages, r_pages, block_tables, kv_lens,
                                     window)
        if selected is not None:
            _traced["sparse_latent_decode"] = "pallas:" + kernels.SPARSE_DECODE
            note_decode_group(kernels.SPARSE_DECODE, work)
            return kernels.sparse_latent_decode(
                q_abs[:, 0], _pad_last(q_rope[:, 0], LANES), c_pages, r_pages,
                layer, block_tables, kv_lens, selected[:, 0], scale=scale,
                pages=pages, work=work)[:, None]
        _traced["latent_decode"] = "pallas:paged_latent_decode"
        note_decode_group("paged_latent_decode", work)
        return kernels.paged_latent_decode(
            q_abs[:, 0], _pad_last(q_rope[:, 0], r_pages.shape[-1]),
            c_pages, r_pages, layer,
            block_tables, kv_lens, scale=scale, pages=pages,
            work=work)[:, None]
    tables = block_tables[:, :pages] if pages < ppn else block_tables
    c = gather_kv_pages(c_pages, tables, layer=layer)  # [B, S, C]
    r = gather_kv_pages(r_pages, tables, layer=layer)
    cell = jnp.arange(c.shape[1], dtype=jnp.int32)
    mask = (cell[None, :] < kv_lens[:, None])[:, None, :]  # [B, 1, S]
    if selected is None:
        _traced["latent_decode"] = "xla"
    else:
        _traced["sparse_latent_decode"] = "xla"
        mask, r = mask & selected, r[..., :LANES]
    return _latent_attend(q_abs, q_rope, c, r, mask, scale)


# ---------------------------------------------------------------------------
# Learned sparse attention (DeepSeek-V3.2's "DSA", models/dots3_note.py,
# docs/sparse-attention.md): beside its latent and rope cell a token leaves
# an INDEX KEY k^I [Di]; a query's indexer scores every cell it may see,
# I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s]) over its Hi index heads in
# float32, and the query attends over the `k` cells of largest I alone — ties
# to the LOWER position, all cells while it sees no more than k. The choice
# is EXACT (no approx_max_k): the k-th largest score is found by a search
# over the scores' bits and the ties at it are counted by position.
# ---------------------------------------------------------------------------

INDEX_KEY_BLOCK = 2048  # cells a step of index_scores holds head scores for
EXTEND_KEY_PAGES = 8  # pages a step of _latent_extend_blocked attends over


def index_scores(
    q_index: jnp.ndarray,  # [B, T, Hi, Di]
    weights: jnp.ndarray,  # [B, T, Hi] f32
    k_index: jnp.ndarray,  # [B, S, Di]
) -> jnp.ndarray:
    """The indexer's scores I [B, T, S] in float32, a block of
    INDEX_KEY_BLOCK cells at a time: the head scores [B, Hi, T, block] are
    the largest value held."""
    s = k_index.shape[1]
    block = INDEX_KEY_BLOCK if s % INDEX_KEY_BLOCK == 0 else s
    weights = weights.astype(jnp.float32)

    def one(k):  # [B, block, Di]
        head = jnp.einsum("bthd,bsd->bhts", q_index, k,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("bhts,bth->bts", jnp.maximum(head, 0.0), weights)

    if block == s:
        return one(k_index)
    b, _, di = k_index.shape
    blocks = jnp.moveaxis(k_index.reshape(b, s // block, block, di), 1, 0)
    return jnp.moveaxis(jax.lax.map(one, blocks), 0, 2).reshape(
        b, q_index.shape[1], s)


def paged_index_scores(
    q_index: jnp.ndarray,  # [B, T, Hi, Di]
    weights: jnp.ndarray,  # [B, T, Hi] f32
    k_pages: jnp.ndarray,  # [L, P, PS, 128 + Di]: the index key's lanes last
    layer,  # int32 scalar
    block_tables: jnp.ndarray,  # [B, PPN] int32
    window: int | None = None,
) -> jnp.ndarray:
    """index_scores over row b's pages of one layer of the pool that holds
    the index keys (the upper lanes of the rope pool's row), [B, T, S] with
    S the cells of the swept pages; every cell is scored, the caller masks
    by position. One query a row goes through the Pallas kernel on an
    unpartitioned TPU (the pool read in place); a chunk's queries gather
    the keys."""
    ps, width = k_pages.shape[2:]
    di = q_index.shape[-1]
    pages = _window_pages(block_tables, ps, window)
    if _pallas_enabled() and q_index.shape[1] == 1 and width == 2 * di:
        from llmlb_tpu.ops import pallas_attention as kernels

        _traced["index_scores"] = "pallas:" + kernels.INDEX_SCORES
        return kernels.index_scores_decode(
            q_index[:, 0], weights[:, 0], k_pages, layer, block_tables,
            pages=pages)[:, None]
    _traced["index_scores" if q_index.shape[1] == 1
            else "index_scores_chunk"] = "xla"
    k = gather_kv_pages(k_pages, block_tables[:, :pages], layer=layer)
    return index_scores(q_index, weights, k[..., width - di:])


def _ordered_bits(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 whose order is the floats' (-0.0 as +0.0)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_mask(scores: jnp.ndarray, valid: jnp.ndarray, k: int) -> jnp.ndarray:
    """[..., S] bool: the `k` entries of largest `scores` (float32, finite)
    among those `valid` says may be chosen, ties to the LOWER index; all of
    the valid ones where they are no more than k. Exact: the k-th largest
    value is found a bit at a time (32 counts over the row: the largest
    threshold that at least k entries reach), and of the entries AT it the
    first by index fill what is left. No sort, no approx_max_k."""
    keys = jnp.where(valid, _ordered_bits(scores.astype(jnp.float32)),
                     jnp.uint32(0))  # a finite score's bits are above 0

    def bit(i, kth):
        trial = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum(keys >= trial[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, trial, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(keys.shape[:-1], jnp.uint32))
    above = keys > kth[..., None]
    at = keys == kth[..., None]
    left = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    first = jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= left[..., None]
    return valid & (above | (at & first))


def _latent_extend_blocked(q_abs, q_rope, c_pages, r_pages, layer,
                           block_tables, q_positions, selected, scale):
    """paged_latent_extend under a selection as plain einsums — the route
    of the CPU and of a partitioned mesh, and what the Pallas kernel
    (pallas_attention.sparse_latent_extend) is checked against:
    EXTEND_KEY_PAGES pages of every row's table a step, as many steps as
    the longest row's context takes, softmax online in float32
    (pallas_attention._online_update's rule). The largest value held is one
    step's scores [B, H, T, block], in HBM."""
    b, t, h, c_dim = q_abs.shape
    ps = c_pages.shape[2]
    ppn = block_tables.shape[1]
    group = min(EXTEND_KEY_PAGES, ppn)
    steps = -(-ppn // group)
    pad = steps * group - ppn
    tables = jnp.pad(block_tables, ((0, 0), (0, pad)))  # the trash page
    chosen = jnp.pad(selected, ((0, 0), (0, 0), (0, pad * ps)))
    block = group * ps
    q_rope = _pad_last(q_rope, LANES)

    def step(j, carry):
        m, l, acc = carry
        at = (layer, jax.lax.dynamic_slice_in_dim(tables, j * group, group, 1))
        c = c_pages[at].reshape(b, block, c_dim)
        r = r_pages[at][..., :LANES].reshape(b, block, LANES)
        cell = j * block + jnp.arange(block, dtype=jnp.int32)
        seen = (cell[None, None, :] <= q_positions[:, :, None]
                ) & jax.lax.dynamic_slice_in_dim(chosen, j * block, block, 2)
        scores = (jnp.einsum("bthc,bsc->bhts", q_abs, c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bthr,bsr->bhts", q_rope, r,
                               preferred_element_type=jnp.float32)) * scale
        scores = jnp.where(seen[:, None], scores, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        fix = jnp.exp(m - m_new)
        # a masked cell's weight is exp(-1e30 - m) = 0 once the row has met
        # a cell it sees; what a row gathers before that (weights of 1 where
        # m is still -1e30) the first real score wipes: `fix` is then 0
        p = jnp.exp(scores - m_new)
        l = l * fix + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * fix + jnp.einsum("bhts,bsc->bhtc", p.astype(c.dtype), c,
                                     preferred_element_type=jnp.float32)
        return m_new, l, acc

    live = jnp.minimum(-(-(jnp.max(q_positions) + 1) // block), steps)
    _, l, acc = jax.lax.fori_loop(0, live, step, (
        jnp.full((b, h, t, 1), _NEG_INF, jnp.float32),
        jnp.zeros((b, h, t, 1), jnp.float32),
        jnp.zeros((b, h, t, c_dim), jnp.float32)))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.transpose(0, 2, 1, 3).astype(q_abs.dtype)
