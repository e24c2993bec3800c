"""Pallas TPU attention kernels: ragged paged flash-decode/extend + causal
flash-prefill.

These are the hot ops of the serving engine (SURVEY.md §7 phase 4: "ragged paged
attention Pallas kernel"). The XLA einsum paths in ops/attention.py are the
correctness baselines; these kernels replace them on TPU:

- `paged_flash_decode` (+ `_quant`): one-token GQA attention against the KV
  page pool. The grid is a WORK-LIST of the live rows' pages, a GROUP of a
  row's consecutive pages an item (`decode_work_list`; `decode_group`
  sizes the group from the shapes: about a megabyte a grid step), a row's
  items consecutive, so Pallas's grid pipeline double-buffers the next
  group's DMAs behind the current group's compute; its length is a
  run-time value. Online softmax (m/l/acc) lives in VMEM scratch across a
  row's items. The item arrays and the per-row `kv_lens` arrive via scalar
  prefetch (SMEM). A row of length 0 is not live (a serving batch's freed,
  never-used and prefilling slots): it takes one grid step that writes
  zeros and reads no page. Decode cost scales with the pages the live rows
  hold — not with the row capacity, the table's width or the batch's
  window bucket. A grid step takes its pages as they are stored, one under
  another [G*PS*K, D], and every query head against them in ONE masked
  product (`_decode_item`): no per-head slice of a page, no product a page.
- `paged_flash_extend` (+ `_quant`): a chunk of queries against the pool
  (chunked prefill, speculative verify, a block pass of generation by
  diffusion), the stacked pool read in place at (layer, page of the row's
  table) as in decode. What a grid step does with its page follows the q
  block's size (`extend_body`, from the shapes alone): a few queries take
  the page as it is stored, in one masked product as decode does; a prefill
  chunk's 128 take it a KV head at a time (`_extend_item`).
- `flash_prefill`: causal self-attention over bucketed prompts. Grid is
  (batch, q_block, kv_block); fully-future KV blocks (k_start > q_end) skip
  compute, giving the ~2x causal FLOP saving dense XLA attention leaves on the
  table. The GQA group dim is folded into the q-row dim so the MXU sees
  [BLK_Q*G, D] x [D, BLK_K] matmuls instead of G tiny ones.

Mosaic tiling: blocks always take the FULL trailing (heads, head_dim) dims —
the lowering requires the last two block dims be (8,128)-aligned *or* equal to
the array dims, and "equal" holds for any head count this way. The prefill
kernel, and the extend kernels at a large q block, iterate KV heads with a
static (unrolled) loop inside the kernel; the decode kernels, and the extend
kernels at a small q block, take all of a page's heads at once.

Numerics match the XLA baselines: fp32 scores/softmax/accumulation
(`preferred_element_type`), finite -1e30 masking (fully-masked rows stay NaN-free).

Multi-device note: a `pallas_call` is opaque to XLA's sharding propagation, so
the dispatcher in ops/attention.py only routes here when the computation is not
partitioned over devices (single-chip serving, or inside `shard_map`).

The reference has no counterpart (it proxies inference — SURVEY.md L0); design
follows the public ragged-paged-attention pattern (PAPERS.md).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmlb_tpu.ops.attention import _block_end

_NEG_INF = -1e30  # finite: keeps fully-masked softmax rows NaN-free


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _online_update(m_ref, l_ref, acc_ref, idx, scores, v):
    """One online-softmax accumulation step into scratch rows `idx`."""
    m_prev = m_ref[idx]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)  # f32
    l_ref[idx] = l_ref[idx] * correction + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[idx] = acc_ref[idx] * correction + pv
    m_ref[idx] = m_new


# ---------------------------------------------------------------------------
# Paged decode: q [B, H, D] vs the stacked page pool [L, P, PS, K, D] at one
# layer. The grid is a work-list of the live rows' pages, a group an item.
# ---------------------------------------------------------------------------


class DecodeWork(NamedTuple):
    """The paged decode kernels' grid: one item per GROUP of consecutive
    logical pages a row attends over, rows in order and a row's groups in
    order. Built by `decode_work_list` once a decode step and shared by
    every layer's call. The group G is the arrays' own: `pool_page_of` holds
    G entries an item."""

    count: jnp.ndarray  # [] int32 — items in use: the grid's run-time length
    row_of: jnp.ndarray  # [W] int32 — the row item i belongs to
    page_of: jnp.ndarray  # [W] int32 — its first logical page within that row
    pool_page_of: jnp.ndarray  # [W * G] int32 — the pool pages its step fetches

    @property
    def group(self) -> int:
        """Pages an item holds (static: the arrays' shapes)."""
        return self.pool_page_of.shape[0] // self.row_of.shape[0]


def _swept_pages(block_tables, pages: int | None) -> int:
    """Pages of a row the decode kernels may visit: `pages`, within the
    table's width."""
    ppn = block_tables.shape[1]
    return ppn if pages is None else max(1, min(pages, ppn))


# What a grid step of a paged decode kernel takes of its pools at most, in
# elements: a megabyte of bf16. A grid step costs about 0.3 us over its
# bytes whatever they are (scripts/decode_page_cost.py, PERF.md §6 PR 54,
# PR 58), and a page of 2 KV heads is 131 KB, 0.16 us of the memory's time.
_GROUP_ELEMENTS = (1 << 20) // 2
# ... in pages: a page of the group is a block operand of the call, whose
# index map every program that holds the kernel traces and lowers at every
# start (4.7 ms a block; at 8 pages a cell's set-up read a fifth more on its
# prewarm thread), and a step's product covers the whole group whatever of
# it is live (2 pages a row at a group of 8 cost what 8 do)
_GROUP_MAX = 4


def decode_group(page_elements: int, sweep: int) -> int:
    """How many of a row's pages one grid step of a paged decode kernel
    takes: a function of the shapes alone, the same where the work-list is
    built and where the kernel is called. `page_elements` is what one page
    holds in all the kernel's pools as they are stored — PS x K x (D + Dv)
    of a pool with a head axis, PS x (C + R) of the latent pools (R the
    rope's whole tile), PS x (K*D + K*Dv) of the flat ones. As many pages as
    make about a megabyte of bf16 — 2 at 8 KV heads of 128, 4 at 4 and at 2
    (half a megabyte there), 1 at 32; 4 latent pages of 512 + 128, 3 flat
    ones of 4 x 192 + 4 x 128 — no more than the `sweep` a row has, and no
    more than 4."""
    return max(1, min(_GROUP_ELEMENTS // page_elements, sweep, _GROUP_MAX))


def decode_work_list(
    block_tables: jnp.ndarray,  # [B, PPN] int32 — logical page i of row b
    kv_lens: jnp.ndarray,  # [B] int32 — valid length per row; 0 = not live
    *,
    page_size: int,
    pages: int | None = None,  # static: at most the first `pages` pages a row
    kv_from: jnp.ndarray | None = None,  # [B] int32 — a row's first cell read
    group: int = 1,  # static: logical pages an item holds
) -> DecodeWork:
    """The work-list of one decode step. A row of length n attends over its
    ceil(n / page_size) pages — at most `pages`: what lies beyond is not
    attended over, as the XLA route's sliced table has it — and contributes
    one item per `group` of them, the last group short where the pages are
    no multiple. A row of length 0 (not live: freed, never used, prefilling)
    contributes ONE item, on which the kernel writes that row's output as
    zeros and computes nothing. W = B x ceil(pages / group) is static,
    `count` is a run-time value.

    An item names `group` pool pages, entry g the page its step's g-th KV
    block fetches. An entry that stands for no page (a short last group's,
    a row's that is not live) repeats what the g-th block fetched last —
    before it has fetched any, ONE page of the step, the first live item's
    first — and a block whose index repeats is not fetched again: a step
    reads each live page once and, for each of the G blocks that no row is
    long enough to fill, that one page once. The kernel masks such an
    entry's cells by the row's length, and what it repeats is a page a live
    row attends over (a masked cell's weight is an exact 0, and 0 x NaN is
    not); the product still covers the whole group.

    `kv_from` is a LOWER bound a row (a model's sliding window; not the
    `pages` bucket, which bounds from above): a row's items cover only the
    logical pages that hold cells `kv_from <= c < n`, `page_of` the logical
    index of an item's first, and logical page p is column p mod PPN of the
    table — a table as wide as the context is read as it always was, a BAND
    of R pages a row (models/afmoe.py) wraps, a page at a time inside a
    group too. The caller keeps a row's span within `pages` pages."""
    b, ppn = block_tables.shape
    sweep = _swept_pages(block_tables, pages)
    groups = -(-sweep // group)  # items of a row at most
    lens = kv_lens.astype(jnp.int32)
    ends = -(-lens // page_size)  # [B] a row's pages up to its length
    first = None
    if kv_from is not None:
        first = jnp.clip(kv_from.astype(jnp.int32), 0,
                         jnp.maximum(lens - 1, 0)) // page_size
        ends = ends - first
    row_pages = jnp.clip(ends, 1, sweep)  # [B] pages of a row in the list
    per_row = -(-row_pages // group)  # [B] items of a row
    end = jnp.cumsum(per_row)
    item = jnp.arange(b * groups, dtype=jnp.int32)
    # [W, B]: the rows that end at or before item i are the rows before its
    # own, and their items are the items before its row's first
    ended = item[:, None] >= end[None, :]
    # items past `count` are never visited; they only have to index in range
    row_of = jnp.minimum(jnp.sum(ended, axis=1, dtype=jnp.int32), b - 1)
    page_of = group * jnp.clip(
        item - jnp.sum(jnp.where(ended, per_row[None, :], 0), axis=1),
        0, groups - 1)
    # [W, G]: the pages of an item, and which of them are pages of its row
    # (an item's first always is, where the row is live)
    slot = jnp.arange(group, dtype=jnp.int32)[None, :]
    within = page_of[:, None] + slot
    real = jnp.logical_and(
        lens[row_of][:, None] > 0,
        jnp.logical_or(slot == 0, within < row_pages[row_of][:, None]))
    column = jnp.minimum(within, sweep - 1)
    if first is not None:
        page_of = first[row_of] + page_of
        column = (first[row_of][:, None] + within) % ppn
    # the nearest item at or before i whose g-th entry reads a page (item
    # 0's, for an entry 0 before any does); for a later entry before any
    # does, the first live item's first page, the same for every such item:
    # one fetch, and a block in VMEM beside a live row's page is always a
    # page some live row attends over
    nearest = jax.lax.cummax(jnp.where(real, item[:, None], -1), axis=0)
    fetched = block_tables.astype(jnp.int32)[row_of[:, None], column]
    pool_page_of = jnp.take_along_axis(fetched, jnp.maximum(nearest, 0),
                                       axis=0)
    if group > 1:
        pool_page_of = jnp.where(
            jnp.logical_or(nearest >= 0, slot == 0), pool_page_of,
            fetched[jnp.argmax(real[:, 0]), 0])
    return DecodeWork(end[-1], row_of, page_of, pool_page_of.reshape(-1))


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as a [1] int32 scalar-prefetch operand: a run-time
    value in SMEM, so every layer of a decode program lowers to the same
    kernel."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


# Index maps of the decode grid: item i, then the scalar-prefetch operands.
# A KV block's map takes the entry `g` of the item's `group` it fetches.


def _group_entry(i, g: int, group: int):
    return i if group == 1 else i * group + g


def _pool_page_map(g, group, i, layer, row_of, page_of, pool_page_of, *lens):
    """KV values [L, P, PS, K, D]: the item's g-th pool page, of the layer."""
    return (layer[0], pool_page_of[_group_entry(i, g, group)], 0, 0, 0)


def _pool_rows_map(g, group, i, layer, row_of, page_of, pool_page_of, *lens):
    """KV values seen as [L, P, PS*K, D]: the same page, as its rows."""
    return (layer[0], pool_page_of[_group_entry(i, g, group)], 0, 0)


def _layer_scale_map(g, group, i, layer, row_of, page_of, pool_page_of,
                     *lens):
    """KV scales [P, PS, K] of one layer of an int8 pool: the same page."""
    return (pool_page_of[_group_entry(i, g, group)], 0, 0)


def _row_map(i, layer, row_of, page_of, pool_page_of, *lens):
    """q and out [B, H, D]: the item's row, for every item of the row."""
    return (row_of[i], 0, 0)


def _stacked(refs):
    """The group's blocks [1, N, ...] one under another: [G*N, ...]."""
    blocks = [ref[0] for ref in refs]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)


def _row_item(row_of_ref, page_of_ref, kv_lens_ref, *, block_k: int,
              sweep: int, group: int, kv_from_ref=None):
    """Item i of the work-list as a kernel body reads it: (`s`, the item's
    first logical page; its row's length — at a group over 1 no further than
    the `pages` a row is read to, which a group may reach past; the row's
    first and last logical page; the row's lower bound, None without
    `kv_from_ref`)."""
    i = pl.program_id(0)
    s = page_of_ref[i]
    kv_len = kv_lens_ref[row_of_ref[i]]
    if kv_from_ref is None:
        first, kv_from = 0, None
        last = jnp.clip(pl.cdiv(kv_len, block_k), 1, sweep) - 1
        if group > 1:
            kv_len = jnp.minimum(kv_len, sweep * block_k)
    else:
        kv_from = jnp.clip(kv_from_ref[row_of_ref[i]], 0,
                           jnp.maximum(kv_len - 1, 0))
        first = kv_from // block_k
        last = jnp.maximum(pl.cdiv(kv_len, block_k), 1) - 1
    return s, kv_len, first, last, kv_from


def _empty_softmax(m_ref, l_ref, acc_ref):
    """The online softmax's scratch before a row's first item."""
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _holds_last_page(s, last, group: int):
    """Whether the item of `group` pages from `s` on is its row's last."""
    return s == last if group == 1 else s + group > last


def _decode_item(row_of_ref, page_of_ref, kv_lens_ref, q_ref, o_ref,
                 m_ref, l_ref, acc_ref, pages, *,
                 block_k: int, sweep: int, num_kv: int, scale: float,
                 group: int = 1, sink_ref=None, kv_from_ref=None):
    """One grid step of a paged decode kernel: item i of the work-list is
    the `group` logical pages from `s` on of row `row`. Online softmax
    (m/l/acc) lives in VMEM scratch from a row's first item to its last. A
    row of length 0 has one item, computes nothing and is written as zeros
    (l == 0).

    The pages are taken as they are stored: `pages()` gives the step's keys
    and values as [G*PS*K, D], row (g*PS + t)*K + h the vector of cell t of
    the group's g-th page and KV head h, and all H = K*G' query heads
    (head-major: row r belongs to KV head r // G') meet the whole group in
    ONE product, one softmax update and one product: the body is the same
    size whatever the group. Entry (r, c) of the scores counts where column
    c's KV head is row r's and its cell is live; every other entry is
    -1e30, whose exp is exactly 0, so the result is the attention of each
    head over its own keys. A column's cell is s*PS + c // K whichever page
    of the group holds it, so the cells of a short last group's missing
    pages lie at or past the row's length and are masked with the rest. The
    other heads' columns are work the MXU does for nothing: 4*H*D*PS*K FLOP
    a page against its 4*PS*K*D bytes, H FLOP a byte (32 at Mistral-7B's
    heads, 64 at 64) where the chip's ridge is about 240 — the kernel stays
    bound by its bytes. (Slicing a head out of the page instead takes one
    sublane of every tile, 2*K times a page, for K products four rows tall:
    1.6 us a page of 512 KB whose bytes take 0.64, PERF.md §6, PR 43.)

    The values may be narrower than the keys (o, acc [H, Dv]). `sink_ref`
    ([H, 1] f32), where given, is a learnt logit a head that enters the
    softmax's denominator and takes no value: a row starts from m = sink,
    l = 1 (exp(sink - m)), acc = 0, and goes on as any other.

    `kv_from_ref` ([B] in SMEM), where given, is each row's LOWER bound
    (decode_work_list's `kv_from`): the row's items start at the page that
    holds that cell and `s` is the item's first LOGICAL page, so the mask
    holds at both ends of the span — the oldest page's cells below the
    bound, the newest page's at or past the length."""
    s, kv_len, first, last, kv_from = _row_item(
        row_of_ref, page_of_ref, kv_lens_ref, block_k=block_k, sweep=sweep,
        group=group, kv_from_ref=kv_from_ref)

    @pl.when(s == first)
    def _init():
        if sink_ref is None:
            _empty_softmax(m_ref, l_ref, acc_ref)
        else:
            m_ref[:] = sink_ref[:]
            l_ref[:] = jnp.ones_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(s * block_k < kv_len)
    def _compute():
        q = q_ref[0]  # [H, D]
        k, v = pages()  # [G*PS*K, D], [G*PS*K, Dv]
        heads = q.shape[0]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, G*PS*K]
        col = jax.lax.broadcasted_iota(
            jnp.int32, (1, group * block_k * num_kv), dimension=1)
        row = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), dimension=0)
        own_head = col % num_kv == row // (heads // num_kv)
        cell = s * block_k + col // num_kv
        keep = jnp.logical_and(own_head, cell < kv_len)
        if kv_from is not None:
            keep = jnp.logical_and(keep, cell >= kv_from)
        scores = jnp.where(keep, scores, _NEG_INF)
        _online_update(m_ref, l_ref, acc_ref, Ellipsis, scores, v)

    @pl.when(_holds_last_page(s, last, group))
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _paged_decode_kernel(
    # scalar prefetch (SMEM); the layer and the pool pages are consumed by
    # the BlockSpec index maps, which pick what each grid step DMAs
    layer_ref, row_of_ref, page_of_ref, pool_page_of_ref, kv_lens_ref,
    *refs,
    group: int, sink: bool = False, bound: bool = False, **kw,
):
    """`refs`, in the call's order: the rows' lower bounds [B] (SMEM, the
    sixth scalar-prefetch operand) where `bound`; q [1, H, D]; the sinks
    [H, 1] f32 where `sink`; the group's key blocks, G of [1, PS*K, D], and
    its value blocks, G of [1, PS*K, Dv]; the output [1, H, Dv]; the
    scratch m, l [H, 1] and acc [H, Dv], f32."""
    del layer_ref, pool_page_of_ref
    refs = list(refs)
    kv_from_ref = refs.pop(0) if bound else None
    q_ref = refs.pop(0)
    sink_ref = refs.pop(0) if sink else None
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * group:]
    _decode_item(row_of_ref, page_of_ref, kv_lens_ref, q_ref, o_ref,
                 m_ref, l_ref, acc_ref,
                 lambda: (_stacked(k_refs), _stacked(v_refs)),
                 group=group, sink_ref=sink_ref, kv_from_ref=kv_from_ref,
                 **kw)


def _dequantized_pages(k_refs, ks_refs, v_refs, vs_refs, dtype):
    """A group of int8 pages' keys and values, G of [1, PS, K, D], times
    their scales, G of [1, PS, K], in `dtype` and as the group's [G*PS*K,
    D] rows."""
    def dequantized(refs, scale_refs):
        return (_stacked(refs).astype(jnp.float32)
                * _stacked(scale_refs)[:, :, None]).astype(dtype)

    k, v = dequantized(k_refs, ks_refs), dequantized(v_refs, vs_refs)
    cells, num_kv, d = k.shape
    return k.reshape(cells * num_kv, d), v.reshape(cells * num_kv, d)


def _paged_decode_quant_kernel(
    layer_ref, row_of_ref, page_of_ref, pool_page_of_ref, kv_lens_ref,
    q_ref,  # [1, H, D]
    *refs,
    group: int, **kw,
):
    """Int8 page pool + per-vector f32 scales: the scale arrays [P, PS, K]
    ride the same work-list as the values (their index maps pick the
    identical pool pages per grid step), and the whole group dequantizes in
    VMEM right before the product — HBM moved int8 bytes. The blocks keep
    the pool's [PS, K, D] (a scale [PS, K] meets its vector there) and take
    the body's [G*PS*K, D] once they are in q's dtype. `refs`: the group's
    key blocks, G of [1, PS, K, D] int8, their scales, G of [1, PS, K] f32,
    the values and their scales likewise; the output [1, H, D]; the
    scratch."""
    del layer_ref, pool_page_of_ref
    k_refs, ks_refs, v_refs, vs_refs = (
        refs[n * group:(n + 1) * group] for n in range(4))
    o_ref, m_ref, l_ref, acc_ref = refs[4 * group:]

    def pages():
        return _dequantized_pages(k_refs, ks_refs, v_refs, vs_refs,
                                  q_ref.dtype)

    _decode_item(row_of_ref, page_of_ref, kv_lens_ref, q_ref, o_ref,
                 m_ref, l_ref, acc_ref, pages, group=group, **kw)


def _paged_decode_call(kernel, kv_blocks, kv_operands, queries, layer,
                       block_tables, kv_lens, work, *, page_size,
                       page_elements, out_dim, pages, interpret, acc_dim=None,
                       name=None, kv_from=None, sink=None, **kernel_kw):
    """The pallas_call the paged decode kernels share: `grid=(work.count,)`
    — a run-time length — over the work-list's items; the blocks of
    `queries` (each [B, H, .]) and of the output [B, H, out_dim] follow the
    item's row, the KV blocks its pool pages: `kv_blocks` gives each operand
    of `kv_operands` its (block shape, index map), and the call hands the
    operand in once a page of the group, the g-th block spec fetching the
    item's g-th pool page. The group is the work-list's own: `decode_group`'s
    of `page_elements` where `work` is built here. `acc_dim`: the
    accumulator's width where it is not the output's; `name`: the call's
    name in a device trace where it is not the calling function's; `kv_from`
    ([B]): a sixth scalar-prefetch operand, the rows' lower bounds; `sink`
    ([H, 1] f32): an operand behind the queries, whole every step;
    `kernel_kw`: the kernel's own static keywords beside the page size, the
    sweep and the group."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, _ = queries[0].shape
    sweep = _swept_pages(block_tables, pages)
    if work is None:
        work = decode_work_list(
            block_tables, kv_lens, page_size=page_size, pages=pages,
            kv_from=kv_from, group=decode_group(page_elements, sweep))
    group = work.group
    bounds = () if kv_from is None else (kv_from.astype(jnp.int32),)

    def row_spec(width):
        return pl.BlockSpec((1, h, width), _row_map, memory_space=pltpu.VMEM)

    sinks, sink_specs = (), []
    if sink is not None:
        sinks = (sink,)
        sink_specs = [pl.BlockSpec(sink.shape, lambda i, *_: (0, 0),
                                   memory_space=pltpu.VMEM)]
    kv_specs = [
        pl.BlockSpec(block, functools.partial(index_map, g, group),
                     memory_space=pltpu.VMEM)
        for block, index_map in kv_blocks for g in range(group)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + len(bounds),
        grid=(work.count,),
        in_specs=[*(row_spec(q.shape[-1]) for q in queries), *sink_specs,
                  *kv_specs],
        out_specs=row_spec(out_dim),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, out_dim if acc_dim is None else acc_dim),
                       jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, block_k=page_size, sweep=sweep, group=group,
                          **kernel_kw),
        out_shape=jax.ShapeDtypeStruct((b, h, out_dim), queries[0].dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        **({} if name is None else {"name": name}),
    )(_layer_operand(layer), work.row_of, work.page_of, work.pool_page_of,
      kv_lens.astype(jnp.int32), *bounds, *queries, *sinks,
      *(operand for operand in kv_operands for _ in range(group)))


@functools.partial(jax.jit, static_argnames=("pages", "interpret", "name"))
def paged_flash_decode(
    q: jnp.ndarray,  # [B, H, D]
    k_pages: jnp.ndarray,  # [L, P, PS, K, D] — global page pool, all layers
    v_pages: jnp.ndarray,  # [L, P, PS, K, Dv] — Dv = D, or narrower values
    layer,  # int32 scalar — the layer of the pool to attend over
    block_tables: jnp.ndarray,  # [B, PPN] int32 — logical page i of row b
    kv_lens: jnp.ndarray,  # [B] int32 — valid logical length; 0 = not live
    *,
    pages: int | None = None,  # static: a row's first `pages` pages at most
    work: DecodeWork | None = None,  # decode_work_list of the same operands
    interpret: bool | None = None,
    sink: jnp.ndarray | None = None,  # [H] — a logit a head, without a value
    name: str | None = None,  # static: the call's name in a device trace
    kv_from: jnp.ndarray | None = None,  # [B] int32 — a row's first cell read
) -> jnp.ndarray:
    """Ragged PAGED one-token GQA decode attention. Returns [B, H, Dv].

    The grid is the work-list of the live rows' pages, a GROUP of a row's
    consecutive pages an item (`decode_work_list`; a decode program builds
    it once a step and hands it to every layer's call as `work`, a direct
    caller may leave it out): its length is a run-time value, so a call
    costs the pages the live rows hold and one step per row that is not
    live, whatever the table's width and `pages`. A grid step costs about
    0.3 us over its bytes, so it takes as many pages as make a megabyte
    (`decode_group`, from the shapes alone; a `work` that is given brings
    its own group): one KV block spec a page of the group over the same
    pool operand, whose index map picks the item's g-th page through the
    prefetched layer index and the item's pool pages — attention reads the
    scattered STACKED pool in place. Neither a contiguous per-row copy nor a per-layer slice
    `pool[layer]` is ever materialized: a pallas_call takes whole buffers
    as operands, so handing it a slice makes XLA copy one layer of the pool
    (105 MB at 400 pages of Mistral-7B width) per call. `layer` is an
    operand, not a Python constant, so the layers of an unrolled decode
    program share one kernel.

    Contract: a row attends over its first min(kv_lens, pages x PS) cells,
    exactly; a row with kv_lens 0 is NOT LIVE — its output is zeros and no
    page of the pool is read for it, whatever its table row holds (the
    engine's freed, never-used and prefilling slot rows). `pages` bounds a
    row's items and the static size of the work-list, not the input shapes.

    The values may be narrower than the keys (scores scale by the KEYS'
    D ** -0.5). `sink` ([H], a learnt logit a head) enters each head's
    softmax denominator and takes no value: p_j = exp(s_j - m) / (exp(sink -
    m) + sum_j exp(s_j - m)). A pool of any other layout with the pages'
    shape serves: a RING a slot [L, slots, cells, K, D] is a pool of one
    page a row, its table [B, 1] the rows' slots (models/mimo_v2.py), and
    its group is 1. Without `sink`, with values as wide as the keys and at
    a group of 1, the call lowers to what it always did.

    `kv_from` ([B], a LOWER bound a row: a model's sliding window — `pages`
    is the context bucket, the first cells a row may read) makes a row
    attend over cells `kv_from <= c < kv_lens` alone: its items are the
    pages that hold them (decode_work_list), logical page p column p mod
    PPN of `block_tables`, masked at both ends. The table may then be a
    BAND of R pages a row that the positions wrap around
    (models/afmoe.py)."""
    if sink is not None and kv_from is not None:
        raise NotImplementedError("a sink beside a lower bound: no kernel")
    layers, pool_pages, ps, num_kv, d = k_pages.shape
    dv = v_pages.shape[-1]
    # a page as its [PS*K, D] rows: in the chip's memory the same bytes
    # (XLA compiles the reshape to a bitcast: the pool's two minor
    # dimensions are tiled K rows deep or eight), and the DMA lands the
    # block dense whatever K is
    rows = (layers, pool_pages, ps * num_kv)
    return _paged_decode_call(
        functools.partial(_paged_decode_kernel, sink=sink is not None,
                          bound=kv_from is not None),
        [((None, 1, ps * num_kv, width), _pool_rows_map) for width in (d, dv)],
        (k_pages.reshape(*rows, d), v_pages.reshape(*rows, dv)), (q,), layer,
        block_tables, kv_lens, work, page_size=ps,
        page_elements=ps * num_kv * (d + dv), out_dim=dv, pages=pages,
        interpret=interpret, name=name, kv_from=kv_from,
        sink=(None if sink is None
              else sink.astype(jnp.float32).reshape(q.shape[1], 1)),
        num_kv=num_kv, scale=d**-0.5)


@functools.partial(jax.jit, static_argnames=("pages", "interpret"))
def paged_flash_decode_quant(
    q: jnp.ndarray,  # [B, H, D]
    k_pages: jnp.ndarray,  # [L, P, PS, K, D] int8 — all layers
    k_scales: jnp.ndarray,  # [P, PS, K] f32 — THE LAYER'S, per written K vector
    v_pages: jnp.ndarray,  # [L, P, PS, K, D] int8
    v_scales: jnp.ndarray,  # [P, PS, K] f32
    layer,  # int32 scalar — the layer of the value pools to attend over
    block_tables: jnp.ndarray,  # [B, PPN] int32
    kv_lens: jnp.ndarray,  # [B] int32 — valid logical length; 0 = not live
    *,
    pages: int | None = None,
    work: DecodeWork | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Int8 variant of paged_flash_decode: dequant-on-read inside the
    kernel. Same work-list grid, group and contract; numerics match the XLA
    dequant fallback (f32 dequant -> q.dtype operands -> f32 accumulation).

    The VALUES follow paged_flash_decode's stacked-pool contract: read in
    place at `(layer, page)`, never sliced. The SCALES arrive as the layer's
    slice `scales[layer]`, and have to: Mosaic wants an operand row-major,
    while XLA keeps an f32 [.., PS, K] array with K = 8 heads PS-minor (the
    other way pads K to 128 lanes, 16x the bytes), so a scale operand is
    re-laid-out on every call whatever its rank. On one layer that writes
    26 MB at 400 pages of 128; on the stacked array it writes all L layers
    every call (compiled for a v5e: 32 whole-array copies a decode step,
    PERF.md §6, PR 25)."""
    _, _, ps, num_kv, d = k_pages.shape
    kv_block = ((None, 1, ps, num_kv, d), _pool_page_map)
    scale_block = ((1, ps, num_kv), _layer_scale_map)
    return _paged_decode_call(
        _paged_decode_quant_kernel,
        [kv_block, scale_block, kv_block, scale_block],
        (k_pages, k_scales, v_pages, v_scales), (q,), layer, block_tables,
        kv_lens, work, page_size=ps, page_elements=ps * num_kv * 2 * d,
        out_dim=d, pages=pages, interpret=interpret, num_kv=num_kv,
        scale=d**-0.5)


# ---------------------------------------------------------------------------
# Latent (MLA) paged decode: 32 query heads on ONE shared latent per token.
# The pool is two arrays under the same page ids, c [L, P, PS, C] and
# k_rope [L, P, PS, R]; the latent is key and value at once. R is a whole
# 128-lane tile (the 64 rope numbers, then zeros): Mosaic wants its operands
# tiled (8, 128), and a [.., 64] pool was copied whole into that layout at
# every call (201 MB at 768 pages: the compile's temp bytes said so).
# ---------------------------------------------------------------------------


def _paged_latent_decode_kernel(
    layer_ref, row_of_ref, page_of_ref, pool_page_of_ref, kv_lens_ref,
    qc_ref,  # [1, H, C] — queries carried into the latent space
    qr_ref,  # [1, H, R] — rotated rope part of the queries
    *refs,
    block_k: int, sweep: int, scale: float, group: int,
    selection: bool = False,
):
    """One grid step: item i of the work-list is the `group` pages from `s`
    on of row `row` (_decode_item's contract: online softmax across a row's
    items, a row of length 0 one item written as zeros, the cells of a short
    last group's missing pages masked by the row's length). Every head
    attends over the same [G*PS, C] latent tiles, one under another: scores
    are two products (latent part, rope part), the values are the tiles
    themselves — two products, one update and one mix a step whatever the
    group. `refs`: the group's latent blocks, G of [1, PS, C], and its rope
    blocks, G of [1, PS, R]; under `selection` a third block a page, the
    row's selection of the page's cells [1, 1, PS] f32 (1 chosen, 0 not: a
    cell enters the softmax iff it is live AND chosen); the output
    [1, H, C]; the scratch m, l [H, 1] and acc [H, C], f32."""
    del layer_ref, pool_page_of_ref
    c_refs, r_refs = refs[:group], refs[group:2 * group]
    sel_refs = refs[2 * group:3 * group] if selection else ()
    o_ref, m_ref, l_ref, acc_ref = refs[2 * group + len(sel_refs):]
    s, kv_len, _, last, _ = _row_item(
        row_of_ref, page_of_ref, kv_lens_ref, block_k=block_k, sweep=sweep,
        group=group)

    @pl.when(s == 0)
    def _init():
        _empty_softmax(m_ref, l_ref, acc_ref)

    @pl.when(s * block_k < kv_len)
    def _compute():
        col = s * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, group * block_k), dimension=1)
        c = _stacked(c_refs)  # [G*PS, C]
        nt = (((1,), (1,)), ((), ()))
        scores = (
            jax.lax.dot_general(qc_ref[0], c, nt,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qr_ref[0], _stacked(r_refs), nt,
                                  preferred_element_type=jnp.float32)
        ) * scale  # [H, G*PS]
        keep = col < kv_len
        if selection:
            chosen = [ref[0] for ref in sel_refs]  # G of [1, PS]
            keep = jnp.logical_and(keep, (
                chosen[0] if group == 1
                else jnp.concatenate(chosen, axis=1)) > 0.5)
        scores = jnp.where(keep, scores, _NEG_INF)
        _online_update(m_ref, l_ref, acc_ref, Ellipsis, scores, c)

    @pl.when(_holds_last_page(s, last, group))
    def _finalize():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _headless_pools(*pools):
    """`_paged_decode_call`'s blocks and page elements for pools without a
    head axis [L, P, PS, W]: a page of each as it is stored, [PS, W]."""
    ps = pools[0].shape[2]
    widths = [pool.shape[-1] for pool in pools]
    return dict(
        kv_blocks=[((None, 1, ps, w), _pool_rows_map) for w in widths],
        kv_operands=pools, page_size=ps, page_elements=ps * sum(widths))


@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret",
                                             "name"))
def paged_latent_decode(
    q_abs: jnp.ndarray,  # [B, H, C]
    q_rope: jnp.ndarray,  # [B, H, R]
    c_pages: jnp.ndarray,  # [L, P, PS, C] — latent pool, all layers
    r_pages: jnp.ndarray,  # [L, P, PS, R] — shared rotated keys
    layer,  # int32 scalar
    block_tables: jnp.ndarray,  # [B, PPN] int32
    kv_lens: jnp.ndarray,  # [B] int32 — valid logical length; 0 = not live
    *,
    scale: float,
    pages: int | None = None,
    work: DecodeWork | None = None,
    interpret: bool | None = None,
    name: str = "paged_latent_decode",  # the call's name in a device trace
) -> jnp.ndarray:
    """Ragged PAGED one-token ABSORBED latent attention. Returns the mix of
    latents [B, H, C]. Grid, `work`, `layer`, `pages` and the rows that are
    not live: paged_flash_decode's contract word for word — the work-list of
    the live rows' pages, a GROUP of a row's consecutive pages an item
    (`decode_group` of a page's PS x (C + R) elements: 4 at 512 + 128), the
    stacked pool read in place at (layer, page). A page is fetched once for
    all H heads. At a group of 1 the call lowers to what it always did."""
    c_dim = q_abs.shape[-1]
    return _paged_decode_call(
        _paged_latent_decode_kernel, queries=(q_abs, q_rope), layer=layer,
        block_tables=block_tables, kv_lens=kv_lens, work=work,
        **_headless_pools(c_pages, r_pages), out_dim=c_dim, pages=pages,
        interpret=interpret, name=name, scale=scale)


# ---------------------------------------------------------------------------
# Learned sparse attention (models/dots3_note.py, docs/sparse-attention.md):
# a token leaves an INDEX KEY beside its latent and rope cell, in the upper
# lanes of the rope pool's row [L, P, PS, 128 + Di]; a decode step scores
# every live cell of a row against the row's index queries
# (`index_scores_decode`), the caller picks the top-k of them exactly
# (ops/attention.topk_mask), and the absorbed attention runs over the chosen
# cells alone (`sparse_latent_decode`). Both kernels read WHOLE pages — the
# selection is a mask over the work-list's pages, not a gather of cells: at
# 16 rows x 15k cells that is 0.6 GB a step against 0.2 GB of chosen cells,
# and a later change may read only the pages that hold a chosen cell.
# ---------------------------------------------------------------------------

SPARSE_DECODE = "sparse_latent_decode"  # the calls' names in a device trace
INDEX_SCORES = "index_scores_decode"
_INDEX_GROUPS = (8, 4, 2, 1)  # pages a grid step of the score kernel takes


def _selected_map(sweep, g, group, i, layer, row_of, page_of, pool_page_of,
                  *lens):
    """The selection [B, sweep, 1, PS]: the item's row, its g-th LOGICAL
    page (a short last group's missing pages repeat the row's last: their
    cells lie past the row's length and are masked by it)."""
    return (row_of[i], jnp.minimum(page_of[i] + g, sweep - 1), 0, 0)


@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret"))
def sparse_latent_decode(
    q_abs: jnp.ndarray,  # [B, H, C]
    q_rope: jnp.ndarray,  # [B, H, 128]
    c_pages: jnp.ndarray,  # [L, P, PS, C]
    r_pages: jnp.ndarray,  # [L, P, PS, 128 + Di]: rope cell | index key
    layer,  # int32 scalar
    block_tables: jnp.ndarray,  # [B, PPN] int32
    kv_lens: jnp.ndarray,  # [B] int32 — valid logical length; 0 = not live
    selected: jnp.ndarray,  # [B, S] bool, S the swept pages' cells
    *,
    scale: float,
    pages: int | None = None,
    work: DecodeWork | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """paged_latent_decode over the cells `selected` says alone: the same
    kernel over the same work-list of live pages with one more block a page
    (the row's selection of its cells). Returns the mix of latents [B, H, C].
    The rope pool's row carries the index key in its upper lanes; the block
    read here is its first 128."""
    b, _, c_dim = q_abs.shape
    ps = c_pages.shape[2]
    sweep = _swept_pages(block_tables, pages)
    lanes = q_rope.shape[-1]
    chosen = selected.astype(jnp.float32).reshape(b, sweep, 1, ps)
    return _paged_decode_call(
        _paged_latent_decode_kernel, queries=(q_abs, q_rope), layer=layer,
        block_tables=block_tables, kv_lens=kv_lens, work=work,
        kv_blocks=[((None, 1, ps, c_dim), _pool_rows_map),
                   ((None, 1, ps, lanes), _pool_rows_map),
                   ((None, 1, 1, ps), functools.partial(_selected_map, sweep))],
        kv_operands=(c_pages, r_pages, chosen), page_size=ps,
        page_elements=ps * (c_dim + r_pages.shape[-1]), out_dim=c_dim,
        pages=pages, interpret=interpret, name=SPARSE_DECODE, scale=scale,
        selection=True)


def _index_scores_kernel(layer_ref, tables_ref, q_ref, w_ref, *refs,
                         group: int):
    """One grid step: `group` pages of row b's table. q [1, Hi, Di] against
    the pages' index keys, G of [1, PS, Di]: ReLU of every head's product,
    times the head's weight w [1, Hi, 1] f32, summed over the heads, in
    float32. The output block is the pages' cells [1, 1, 1, G*PS]."""
    del layer_ref, tables_ref
    k_refs, o_ref = refs[:group], refs[group]
    scores = jax.lax.dot_general(
        q_ref[0], _stacked(k_refs), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [Hi, G*PS]
    o_ref[0, 0] = jnp.sum(jnp.maximum(scores, 0.0) * w_ref[0], axis=0,
                          keepdims=True)


@functools.partial(jax.jit, static_argnames=("pages", "lane_block",
                                             "interpret"))
def index_scores_decode(
    q_index: jnp.ndarray,  # [B, Hi, Di]
    weights: jnp.ndarray,  # [B, Hi] f32, the heads' weights
    k_pages: jnp.ndarray,  # [L, P, PS, W]: the index key in lanes
    # [lane_block * Di, (lane_block + 1) * Di) of a row
    layer,  # int32 scalar
    block_tables: jnp.ndarray,  # [B, PPN] int32
    *,
    pages: int | None = None,
    lane_block: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The learned indexer's scores of one query a row over the first
    `pages` pages of its table: I[b, s] = sum_j w[b, j] ReLU(q[b, j] .
    k[s]) in float32, [B, pages * PS]. EVERY page of the sweep is scored,
    whatever the row's length (a table's unused entries are the trash page):
    the caller masks by position. The pool is read in place at (layer, page,
    the key's lanes)."""
    if interpret is None:
        interpret = _interpret_default()
    b, hi, di = q_index.shape
    ps = k_pages.shape[2]
    sweep = _swept_pages(block_tables, pages)
    group = next(g for g in _INDEX_GROUPS if sweep % g == 0)
    steps = sweep // group

    def page_map(g, bi, ji, layer, tables):
        return (layer[0], tables[bi, ji * group + g], 0, lane_block)

    def row_map(bi, ji, layer, tables):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, steps),
        in_specs=[
            pl.BlockSpec((1, hi, di), row_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hi, 1), row_map, memory_space=pltpu.VMEM),
            *(pl.BlockSpec((None, 1, ps, di), functools.partial(page_map, g),
                           memory_space=pltpu.VMEM) for g in range(group))],
        out_specs=pl.BlockSpec((1, 1, 1, group * ps),
                               lambda bi, ji, layer, tables: (bi, ji, 0, 0),
                               memory_space=pltpu.VMEM),
    )
    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, group=group),
        out_shape=jax.ShapeDtypeStruct((b, steps, 1, group * ps),
                                       jnp.float32),
        grid_spec=grid_spec, interpret=interpret, name=INDEX_SCORES,
    )(_layer_operand(layer), block_tables[:, :sweep].astype(jnp.int32),
      q_index, weights.astype(jnp.float32)[:, :, None],
      *(k_pages for _ in range(group)))
    return out.reshape(b, sweep * ps)


# ---------------------------------------------------------------------------
# A chunk's attention under a selection (an extend chunk of the full layers):
# q [B, T, H, C | 128] against the same two pools, every head of a query
# over ONE latent key block under ONE mask row, so the chunk's (query, head)
# pairs are the rows of a matmul against [cells, C + 128] and the scores
# [rows, cells] live in VMEM between the two products. In XLA
# (ops/attention._latent_extend_blocked) the same step writes them to HBM:
# 268 MB of float32 a block of 1,024 cells at 512 queries x 128 heads, read
# and rewritten by the mask, the maximum, the exponential, a re-laid bf16
# copy and the accumulator's rescale — 2 GB of traffic for 155 GFLOP.
# ---------------------------------------------------------------------------

SPARSE_EXTEND = "sparse_latent_extend"  # the call's name in a device trace
# The q block and the pages a grid step takes at most: the measured pick
# (scripts/extend_select_cost.py on a v5e, PERF.md §6 PR 65: 16 queries x 4
# pages — scores [2048, 512] f32, 4 MB — ran the products at 90% of the MXU
# at 6k and 12k; 8 x 8 within 4% of it, a page a step at 38%: the
# accumulator's rescale a step is the cost a group amortises).
SPARSE_EXTEND_BLOCK_Q = 16
_SPARSE_EXTEND_GROUPS = (4, 2, 1)


def sparse_extend_blocks(queries: int, pages: int) -> tuple[int, int]:
    """(q block, pages a grid step) of `sparse_latent_extend` at a chunk of
    `queries` over a table of `pages`: from the shapes alone. The group is
    the largest of 4, 2, 1 that divides the table (no padded copy of the
    table or the selection)."""
    return (min(SPARSE_EXTEND_BLOCK_Q, queries),
            next(g for g in _SPARSE_EXTEND_GROUPS if pages % g == 0))


def _sparse_extend_kernel(
    layer_ref, tables_ref,
    last_ref,  # [B, NQ] — a q block's last position; -1: all padding
    qc_ref,  # [1, BLK_Q*H, C] — row r the query r // H, head r % H
    qr_ref,  # [1, BLK_Q*H, 128]
    sel_ref,  # [1, BLK_Q, G*PS] f32 — 1 chosen (and seen), 0 not
    *refs,
    block_q: int, heads: int, block_k: int, group: int, scale: float,
):
    """One grid step (row b, q block qi, key group ki): the `group` pages
    from ki * group on of row b's table, their latent tiles one under
    another [G*PS, C] and the rope cells beside them, against the q block's
    BLK_Q * H rows in ONE pair of products, a query's mask row spread over
    its heads. Online softmax (m, l, acc) in VMEM scratch across a q
    block's groups, `_online_update`'s rule; a cell enters iff the
    selection names it (already causal). A group wholly past the q block's
    last position, and a q block wholly of padding, compute nothing (and
    fetch nothing new: the index maps stay on the last group that counts).
    `refs`: G latent blocks [1, PS, C], G rope blocks [1, PS, 128], the
    output as `qc_ref`, then m, l [BLK_Q*H, 1] and acc [BLK_Q*H, C], f32."""
    del layer_ref, tables_ref
    c_refs, r_refs = refs[:group], refs[group:2 * group]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * group:]
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cells = group * block_k

    @pl.when(ki == 0)
    def _init():
        _empty_softmax(m_ref, l_ref, acc_ref)

    @pl.when(ki * cells <= last_ref[b, qi])
    def _compute():
        c = _stacked(c_refs)  # [G*PS, C]
        nt = (((1,), (1,)), ((), ()))
        scores = (
            jax.lax.dot_general(qc_ref[0], c, nt,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qr_ref[0], _stacked(r_refs), nt,
                                  preferred_element_type=jnp.float32)
        ) * scale  # [BLK_Q*H, G*PS]
        keep = sel_ref[0] > 0.5  # [BLK_Q, G*PS]
        scores = jnp.where(
            keep[:, None, :], scores.reshape(block_q, heads, cells),
            _NEG_INF).reshape(block_q * heads, cells)
        _online_update(m_ref, l_ref, acc_ref, Ellipsis, scores, c)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "group",
                                             "interpret"))
def sparse_latent_extend(
    q_abs: jnp.ndarray,  # [B, T, H, C]
    q_rope: jnp.ndarray,  # [B, T, H, 128]
    c_pages: jnp.ndarray,  # [L, P, PS, C]
    r_pages: jnp.ndarray,  # [L, P, PS, 128 (+ Di)]: rope cell | index key
    layer,  # int32 scalar
    block_tables: jnp.ndarray,  # [B, PPN] int32
    q_positions: jnp.ndarray,  # [B, T] int32 — global position of a query
    chunk_lens: jnp.ndarray,  # [B] int32 — valid queries (rest are padding)
    selected: jnp.ndarray,  # [B, T, PPN * PS] bool: topk_mask's, so causal
    *,
    scale: float,
    block_q: int | None = None,
    group: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Absorbed attention of a chunk of queries over the cells `selected`
    names of row b's pages, one layer of the stacked pools read in place at
    (layer, table[b, page]) through the prefetched table. Returns the mix of
    latents [B, T, H, C]. Rows are (query, head) pairs, the chunk as it
    lies: every head of a query meets the same key block under the same
    mask row. Grid (row, q block, key group), the key axis innermost and as
    long as the longest row's context takes (a run-time value); within it a
    group past a q block's last position and a q block past `chunk_lens`
    are skipped — such a block's rows come back as zeros. `selected` alone
    decides what a query attends over: it must be causal, as `topk_mask`'s
    answer is. `block_q`, `group`: `sparse_extend_blocks`' for the shapes;
    only a measurement of the others (scripts/extend_select_cost.py, the
    tests) names them. Same arithmetic as the plain einsums
    (ops/attention._latent_extend_blocked): operands in the pool's dtype,
    float32 products and softmax, the probabilities cast to the pool's
    dtype for the value product, one division at the end."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, h, c_dim = q_abs.shape
    lanes = q_rope.shape[-1]
    ps = c_pages.shape[2]
    ppn = block_tables.shape[1]
    blocks = sparse_extend_blocks(t, ppn)
    blk_q = blocks[0] if block_q is None else min(block_q, t)
    group = blocks[1] if group is None else group
    assert t % blk_q == 0 and ppn % group == 0, (t, blk_q, ppn, group)
    nq, steps, cells = t // blk_q, ppn // group, group * ps
    starts = jnp.arange(nq, dtype=jnp.int32) * blk_q
    last = jnp.where(
        starts[None, :] < chunk_lens[:, None],
        jnp.max(q_positions.reshape(b, nq, blk_q), axis=-1), -1
    ).astype(jnp.int32)
    live = jnp.clip(jnp.max(last) // cells + 1, 1, steps)

    def q_map(bi, qi, ki, layer, tables, last):
        return (bi, qi, 0)

    def counted(bi, qi, ki, last):
        """The key group the step fetches: ki, or the q block's last that
        counts where ki is past it (the block then stays where it is)."""
        return jnp.minimum(ki, jnp.maximum(last[bi, qi], 0) // cells)

    def sel_map(bi, qi, ki, layer, tables, last):
        return (bi, qi, counted(bi, qi, ki, last))

    def page_map(g, bi, qi, ki, layer, tables, last):
        return (layer[0], tables[bi, counted(bi, qi, ki, last) * group + g],
                0, 0)

    rows = blk_q * h

    def q_spec(width):
        return pl.BlockSpec((1, rows, width), q_map, memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nq, live),
        in_specs=[
            q_spec(c_dim), q_spec(lanes),
            pl.BlockSpec((1, blk_q, cells), sel_map, memory_space=pltpu.VMEM),
            *(pl.BlockSpec((None, 1, ps, width),
                           functools.partial(page_map, g),
                           memory_space=pltpu.VMEM)
              for width in (c_dim, lanes) for g in range(group))],
        out_specs=q_spec(c_dim),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, c_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_sparse_extend_kernel, block_q=blk_q, heads=h,
                          block_k=ps, group=group, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, t * h, c_dim), q_abs.dtype),
        grid_spec=grid_spec, interpret=interpret, name=SPARSE_EXTEND,
        # the q block, its output and the accumulator are 14 MB at 16
        # queries x 128 heads, a step's scores 4 MB more
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=48 << 20),
    )(_layer_operand(layer), block_tables.astype(jnp.int32), last,
      q_abs.reshape(b, t * h, c_dim), q_rope.reshape(b, t * h, lanes),
      selected.astype(jnp.float32),
      *(pool for pool in (c_pages, r_pages) for _ in range(group)))
    return out.reshape(b, t, h, c_dim)


# ---------------------------------------------------------------------------
# Flat paged decode: GQA against a pool WITHOUT a head axis, a cell one row
# of all its KV heads side by side — keys [L, P, PS, K*D], values [L, P, PS,
# K*Dv]. For heads whose width is no multiple of 128 lanes on fewer than 8
# KV heads (4 x 192): a pool [.., PS, 4, 192] is tiled four rows deep and
# two lane tiles wide, its view as a page's [PS*4, 192] rows is then no
# bitcast, and XLA copied the whole pool, 441 MB, in front of every call of
# paged_flash_decode (the compile for a described v5e said so, PR 45). A
# row of 768 lanes is six whole tiles: nothing is padded and nothing copied.
# ---------------------------------------------------------------------------


def _paged_flat_decode_kernel(
    layer_ref, row_of_ref, page_of_ref, pool_page_of_ref, kv_lens_ref,
    q_ref,  # [1, H, K*D] — head r's query in its KV head's columns, else 0
    *refs,
    block_k: int, sweep: int, num_kv: int, scale: float, group: int,
):
    """One grid step: item i of the work-list is the `group` pages from `s`
    on of row `row` (_decode_item's contract). A query that is zero outside
    its own KV head's columns meets the whole row of every cell of the group
    in ONE product, [H, K*D] x [K*D, G*PS]: the scores are [H, G*PS], no
    column of another head's to mask. The mix p v is [H, K*Dv]; a head keeps
    its own KV head's Dv columns at the end. `refs`: the group's key blocks,
    G of [1, PS, K*D], and its value blocks, G of [1, PS, K*Dv]; the output
    [1, H, Dv]; the scratch m, l [H, 1] and acc [H, K*Dv], f32."""
    del layer_ref, pool_page_of_ref
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * group:]
    s, kv_len, _, last, _ = _row_item(
        row_of_ref, page_of_ref, kv_lens_ref, block_k=block_k, sweep=sweep,
        group=group)

    @pl.when(s == 0)
    def _init():
        _empty_softmax(m_ref, l_ref, acc_ref)

    @pl.when(s * block_k < kv_len)
    def _compute():
        col = s * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, group * block_k), dimension=1)
        scores = jax.lax.dot_general(
            q_ref[0], _stacked(k_refs), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, G*PS]
        scores = jnp.where(col < kv_len, scores, _NEG_INF)
        _online_update(m_ref, l_ref, acc_ref, Ellipsis, scores,
                       _stacked(v_refs))

    @pl.when(_holds_last_page(s, last, group))
    def _finalize():
        l = l_ref[:]
        heads, dv = o_ref.shape[1], o_ref.shape[2]
        row = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), dimension=0)
        own = row // (heads // num_kv)
        out = jnp.zeros((heads, dv), jnp.float32)
        for kh in range(num_kv):  # static: a lane-aligned slice a KV head
            out = out + jnp.where(own == kh,
                                  acc_ref[:, kh * dv:(kh + 1) * dv], 0.0)
        o_ref[0] = (out / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_kv", "pages", "interpret"))
def paged_flat_decode(
    q: jnp.ndarray,  # [B, H, D]
    k_pages: jnp.ndarray,  # [L, P, PS, K*D] — a cell's KV heads side by side
    v_pages: jnp.ndarray,  # [L, P, PS, K*Dv]
    layer,  # int32 scalar
    block_tables: jnp.ndarray,  # [B, PPN] int32
    kv_lens: jnp.ndarray,  # [B] int32 — valid logical length; 0 = not live
    *,
    num_kv: int,
    pages: int | None = None,
    work: DecodeWork | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Ragged PAGED one-token GQA decode attention over a pool without a
    head axis. Returns [B, H, Dv]. Grid, `work`, `layer`, `pages` and the
    rows that are not live: paged_flash_decode's contract word for word, a
    GROUP of a row's consecutive pages an item (`decode_group` of a page's
    PS x (K*D + K*Dv) elements: 3 at 4 x 192 + 4 x 128). Scores scale by
    D ** -0.5; Dv must be a multiple of 128 lanes. At a group of 1 the call
    lowers to what it always did."""
    b, h, d = q.shape
    kd, kdv = k_pages.shape[-1], v_pages.shape[-1]
    # head r's query in the columns of its KV head r // G, zeros elsewhere
    own = (jnp.arange(h)[:, None] // (h // num_kv)
           == jnp.arange(num_kv)[None, :])  # [H, K]
    q_wide = jnp.where(own[None, :, :, None], q[:, :, None, :],
                       jnp.zeros((), q.dtype)).reshape(b, h, kd)
    return _paged_decode_call(
        _paged_flat_decode_kernel, queries=(q_wide,), layer=layer,
        block_tables=block_tables, kv_lens=kv_lens, work=work,
        **_headless_pools(k_pages, v_pages), out_dim=kdv // num_kv,
        acc_dim=kdv, pages=pages, interpret=interpret,
        name="paged_flat_decode", num_kv=num_kv, scale=d**-0.5)


# ---------------------------------------------------------------------------
# Prefill: causal q [B, T, H, D] vs fresh k/v [B, T, K, D], ragged prompt_lens
# ---------------------------------------------------------------------------


def _prefill_kernel(
    # scalar prefetch
    prompt_lens_ref,  # [B] int32 (SMEM)
    # inputs
    q_ref,  # [1, BLK_Q, K, G, D]
    k_ref,  # [1, BLK_K, K, D]
    v_ref,  # [1, BLK_K, K, D]
    # output
    o_ref,  # [1, BLK_Q, K, G, D]
    # scratch
    m_ref,  # [K, BLK_Q * G, 1] f32
    l_ref,  # [K, BLK_Q * G, 1] f32
    acc_ref,  # [K, BLK_Q * G, D] f32
    *,
    block_q: int,
    block_k: int,
    num_kv: int,
    groups: int,
    scale: float,
    block: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k_blocks = pl.num_programs(2)
    prompt_len = prompt_lens_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    rows = block_q * groups
    # causal skip: the whole KV block is in the future of the whole Q block
    not_all_future = k_start <= _block_end(q_start + block_q - 1, block)
    # ragged skip: the whole KV block is beyond the prompt
    in_prompt = k_start < prompt_len

    @pl.when(jnp.logical_and(not_all_future, in_prompt))
    def _compute():
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), dimension=0)
        col = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), dimension=1
        )
        q_pos = q_start + row // groups
        mask = (col <= _block_end(q_pos, block)) & (col < prompt_len)
        for h in range(num_kv):  # static unroll over KV heads
            q = q_ref[0, :, h].reshape(rows, -1)  # [BLK_Q*G, D]; t slow, g fast
            k = k_ref[0, :, h, :]  # [BLK_K, D]
            v = v_ref[0, :, h, :]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [BLK_Q*G, BLK_K]
            scores = jnp.where(mask, scores, _NEG_INF)
            _online_update(m_ref, l_ref, acc_ref, h, scores, v)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[:] / l_safe).astype(o_ref.dtype)  # [K, BLK_Q*G, D]
        o_ref[0] = out.reshape(num_kv, block_q, groups, -1).transpose(1, 0, 2, 3)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret", "block")
)
def flash_prefill(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, T, K, D]
    v: jnp.ndarray,  # [B, T, K, D]
    prompt_lens: jnp.ndarray,  # [B] int32
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    block: int = 1,
) -> jnp.ndarray:
    """Causal ragged GQA prefill attention, block-causal with `block` > 1
    (ops/attention._block_end). Returns [B, T, H, D] in q.dtype."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, h, d = q.shape
    num_kv = k.shape[2]
    g = h // num_kv
    blk_q = min(block_q, t)
    blk_k = min(block_k, t)
    grid = (b, pl.cdiv(t, blk_q), pl.cdiv(t, blk_k))
    qg = q.reshape(b, t, num_kv, g, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, blk_q, num_kv, g, d),
                lambda bi, qi, si, lens: (bi, qi, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, blk_k, num_kv, d), lambda bi, qi, si, lens: (bi, si, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, blk_k, num_kv, d), lambda bi, qi, si, lens: (bi, si, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, blk_q, num_kv, g, d),
            lambda bi, qi, si, lens: (bi, qi, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((num_kv, blk_q * g, 1), jnp.float32),
            pltpu.VMEM((num_kv, blk_q * g, 1), jnp.float32),
            pltpu.VMEM((num_kv, blk_q * g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel,
            block_q=blk_q,
            block_k=blk_k,
            num_kv=num_kv,
            groups=g,
            scale=d**-0.5,
            block=block,
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, num_kv, g, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(prompt_lens.astype(jnp.int32), qg, k, v)
    return out.reshape(b, t, h, d)


# ---------------------------------------------------------------------------
# Extend body (chunked prefill, verify, a block pass): a q block of BLK_Q
# positions vs one KV block of BLK_K cells; the chunk starts at global
# position start_pos[b] (contiguous positions). One algorithm in two inner
# forms, picked from the shapes by `extend_body`.
# ---------------------------------------------------------------------------

EXTEND_BLOCK_Q = 128  # the paged extend kernels' q block, at most

# The float32 scores ([BLK_Q*H, PS*K]) up to which a grid step takes its page
# as it is stored: the measured crossover (`extend_body`).
_PAGE_BODY_MAX_SCORE_BYTES = 2 << 20


def extend_body(blk_q: int, heads: int, num_kv: int, page_size: int) -> str:
    """Which inner form a grid step of the paged extend kernels takes, from
    the shapes the wrapper sees at trace time: "page" (one masked product
    over the page's [PS*K, D] rows, `_extend_item`) or "heads" (a product a
    KV head on that head's slice of the page).

    The arithmetic: the masked product computes every query row against
    every KV head's columns, K times the FLOPs and K times the softmax
    elements of the per-head form: 4 * R * D * PS*K FLOP a page against its
    4 * PS*K*D bytes, R = blk_q * heads FLOP a byte whatever K and the page
    size, where the chip's ridge is about 240 (`_decode_item`). That puts
    the threshold at 256 rows: a block pass's 8 positions x 32 heads on the
    one side, a prefill chunk's 128 x 32 = 4,096 on the other.

    The measurement (scripts/decode_page_cost.py on a v5e, both forms at
    every q block; PERF.md §6, PR 46) agrees on those two and puts the
    crossover higher between them, so the measured one stands. The
    per-head form pays its 2 * K sublane slices of the stored page whatever
    the q block: 4.1-4.2 us a page of 128 cells at 4 and at 2 KV heads up to
    16 positions, 1.7 at 8 KV heads, and from there it grows with the rows.
    The masked form costs about 3.5 ns a ROW of the q block whatever K
    (0.81 / 1.02 / 0.83 us a page at 256 rows and 4 / 8 / 2 KV heads, 3.6 at
    1,024 at all three): the accumulators' rows, not the MXU, are its cost.
    They meet where a step's scores are 2 MB: the masked form is the faster
    at 4 KV heads through 32 positions x 32 heads (3.62 against 4.68 us), at
    2 through 64 (5.20 against 9.14), at 8 through 16 (1.86 against 1.84, a
    tie; 3.64 against 2.45 at 32). At 8 MB Mosaic refuses the scores the
    VMEM (128 positions at 4 KV heads); 32 ungrouped KV heads are past 2 MB
    at 8 positions."""
    score_bytes = blk_q * heads * page_size * num_kv * 4
    return "page" if score_bytes <= _PAGE_BODY_MAX_SCORE_BYTES else "heads"


def _extend_item(start_pos_ref, chunk_lens_ref, q_ref, o_ref, m_ref, l_ref,
                 acc_ref, load, *, body: str, block_q: int, block_k: int,
                 num_kv: int, groups: int, scale: float, block: int):
    """One grid step (row b, q block qi, KV block ki) of a paged extend
    kernel: online softmax (m/l/acc) lives in VMEM scratch across a q
    block's KV blocks.

    `body` "heads": q and out blocks [1, BLK_Q, K, G, D], scratch [K,
    BLK_Q*G, .]; `load(h)` gives KV head h's [BLK_K, D] keys and values, a
    slice of the stored page, and each head takes its own product, softmax
    update and product.

    `body` "page", the extend twin of `_decode_item`: q and out blocks as
    the chunk lies, [1, BLK_Q*H, D] (row r the query of position r // H and
    head r % H, of KV head (r % H) // G), scratch [BLK_Q*H, .]; `load()`
    gives the step's page as it is stored, [PS*K, D], row t*K + h the
    vector of cell t and KV head h, and every row meets it in ONE product,
    one softmax update and one product. Entry (r, c) counts where column
    c's KV head is row r's and its cell is visible to row r's query; every
    other entry is -1e30, whose exp is exactly 0 (a row's first page always
    holds a visible cell, so its maximum is a real score from there on)."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k_blocks = pl.num_programs(2)
    start = start_pos_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # Skip KV blocks entirely in the future of every query in this Q block
    # (query global positions are start + q_start .. start + q_start+BLK_Q-1),
    # so extend cost scales with the context actually filled, not capacity;
    # also skip Q blocks made entirely of padding rows (beyond chunk_lens) —
    # their zero-initialized output is ignored by the caller.
    useful = jnp.logical_and(
        k_start <= _block_end(start + q_start + block_q - 1, block),
        q_start < chunk_lens_ref[b],
    )

    def _heads_step():
        rows = block_q * groups
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), dimension=0)
        col = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), dimension=1
        )
        q_pos = start + q_start + row // groups  # global position per query
        mask = col <= _block_end(q_pos, block)
        for h in range(num_kv):  # static unroll over KV heads
            q = q_ref[0, :, h].reshape(rows, -1)  # [BLK_Q*G, D]
            k, v = load(h)  # [BLK_K, D] each
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            scores = jnp.where(mask, scores, _NEG_INF)
            _online_update(m_ref, l_ref, acc_ref, h, scores, v)

    def _page_step():
        heads = num_kv * groups
        k, v = load()  # [PS*K, D] each
        scores = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [BLK_Q*H, PS*K]
        col = jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k * num_kv), dimension=1)
        row = jax.lax.broadcasted_iota(
            jnp.int32, (block_q * heads, 1), dimension=0)
        q_pos = start + q_start + row // heads  # global position per query
        keep = jnp.logical_and(
            col % num_kv == row % heads // groups,
            k_start + col // num_kv <= _block_end(q_pos, block))
        scores = jnp.where(keep, scores, _NEG_INF)
        _online_update(m_ref, l_ref, acc_ref, Ellipsis, scores, v)

    pl.when(useful)(_page_step if body == "page" else _heads_step)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        if body == "heads":  # [K, BLK_Q*G, D] -> the block's [BLK_Q, K, G, D]
            out = out.reshape(num_kv, block_q, groups, -1).transpose(1, 0, 2, 3)
        o_ref[0] = out


# ---------------------------------------------------------------------------
# Paged extend (chunked prefill): q chunk [B, T, H, D] vs the stacked page
# pool [L, P, PS, K, D] at one layer, through block tables [B, PPN]; chunk
# starts at start_pos[b].
# ---------------------------------------------------------------------------


# Index maps of the extend grid (row, q block, logical page), then the
# scalar-prefetch operands.


def _extend_q_map(bi, qi, si, layer, tables, starts, lens):
    """q and out [B, T, K, G, D]: the row's q block, for every page."""
    return (bi, qi, 0, 0, 0)


def _extend_q_rows_map(bi, qi, si, layer, tables, starts, lens):
    """q and out seen as [B, T*H, D]: the same q block, as its rows."""
    return (bi, qi, 0)


def _extend_page_map(bi, qi, si, layer, tables, starts, lens):
    """KV values [L, P, PS, K, D]: logical page si of row bi, of the layer."""
    return (layer[0], tables[bi, si], 0, 0, 0)


def _extend_page_rows_map(bi, qi, si, layer, tables, starts, lens):
    """KV values seen as [L, P, PS*K, D]: the same page, as its rows."""
    return (layer[0], tables[bi, si], 0, 0)


def _extend_scale_map(bi, qi, si, layer, tables, starts, lens):
    """KV scales [P, PS, K] of one layer of an int8 pool: the same page."""
    return (tables[bi, si], 0, 0)


def _paged_extend_kernel(
    # scalar prefetch (SMEM); the layer and the table are consumed by the
    # BlockSpec index maps, which walk the block table in logical order, so
    # the logical KV position of grid step `ki` is ki * page_size
    layer_ref, block_tables_ref, start_pos_ref, chunk_lens_ref,
    # inputs
    q_ref,  # [1, BLK_Q, K, G, D] | [1, BLK_Q*H, D]
    k_ref,  # [1, PS, K, D] | [1, PS*K, D]
    v_ref,  # [1, PS, K, D] | [1, PS*K, D]
    # output
    o_ref,  # as q_ref
    # scratch
    m_ref,  # [K, BLK_Q * G, 1] | [BLK_Q * H, 1] f32
    l_ref,  # [K, BLK_Q * G, 1] | [BLK_Q * H, 1] f32
    acc_ref,  # [K, BLK_Q * G, D] | [BLK_Q * H, D] f32
    *, body: str, **kw,
):
    """Shapes as `_extend_item`'s `body` has them: "heads" | "page"."""
    del layer_ref, block_tables_ref

    def kv_head(h):
        return k_ref[0, :, h, :], v_ref[0, :, h, :]

    def page():
        return k_ref[0], v_ref[0]

    _extend_item(start_pos_ref, chunk_lens_ref, q_ref, o_ref, m_ref, l_ref,
                 acc_ref, page if body == "page" else kv_head, body=body, **kw)


def _paged_extend_quant_kernel(
    layer_ref, block_tables_ref, start_pos_ref, chunk_lens_ref,
    q_ref,  # [1, BLK_Q, K, G, D] | [1, BLK_Q*H, D]
    k_ref,  # [1, PS, K, D] int8
    ks_ref,  # [1, PS, K] f32
    v_ref,  # [1, PS, K, D] int8
    vs_ref,  # [1, PS, K] f32
    o_ref,  # as q_ref
    m_ref, l_ref, acc_ref,
    *, body: str, **kw,
):
    """Int8 pool + per-vector f32 scales, dequant-on-read — the verify and
    chunked-prefill counterpart of _paged_decode_quant_kernel. The blocks
    keep the pool's [PS, K, D] in either body (a scale [PS, K] meets its
    vector there); the "page" body dequantizes the whole page and takes its
    [PS*K, D] rows once they are in q's dtype."""
    del layer_ref, block_tables_ref
    dtype = q_ref.dtype

    def kv_head(h):
        k = (k_ref[0, :, h, :].astype(jnp.float32)
             * ks_ref[0, :, h][:, None]).astype(dtype)  # [BLK_K, D]
        v = (v_ref[0, :, h, :].astype(jnp.float32)
             * vs_ref[0, :, h][:, None]).astype(dtype)
        return k, v

    def page():
        return _dequantized_pages([k_ref], [ks_ref], [v_ref], [vs_ref], dtype)

    _extend_item(start_pos_ref, chunk_lens_ref, q_ref, o_ref, m_ref, l_ref,
                 acc_ref, page if body == "page" else kv_head, body=body, **kw)


def _paged_extend_call(q, k_pages, v_pages, scales, layer, block_tables,
                       start_pos, chunk_lens, *, block_q, interpret, block,
                       body=None):
    """The pallas_call both paged extend kernels share: grid (row, q block,
    logical page); q and out blocks follow (row, q block), the KV blocks the
    row's page. `scales`: an int8 pool's (k_scales, v_scales), the layer's;
    None for a pool in q's dtype. `body`: `extend_body`'s answer for the
    shapes; only a measurement of both forms (scripts/decode_page_cost.py,
    the tests) names one."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, h, d = q.shape
    layers, pool_pages, ps, num_kv, _ = k_pages.shape
    g = h // num_kv
    blk_q = min(block_q, t)
    if body is None:
        body = extend_body(blk_q, h, num_kv, ps)
    if body == "page":  # the chunk as it lies: [T*H, D] rows
        q_shape, rows = (b, t * h, d), (blk_q * h,)
        q_spec = pl.BlockSpec((1, blk_q * h, d), _extend_q_rows_map,
                              memory_space=pltpu.VMEM)
    else:
        q_shape, rows = (b, t, num_kv, g, d), (num_kv, blk_q * g)
        q_spec = pl.BlockSpec((1, blk_q, num_kv, g, d), _extend_q_map,
                              memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((None, 1, ps, num_kv, d), _extend_page_map,
                           memory_space=pltpu.VMEM)
    if scales is not None:
        scale_spec = pl.BlockSpec((1, ps, num_kv), _extend_scale_map,
                                  memory_space=pltpu.VMEM)
        kernel = _paged_extend_quant_kernel
        kv_specs = [kv_spec, scale_spec, kv_spec, scale_spec]
        kv_operands = (k_pages, scales[0], v_pages, scales[1])
    else:
        kernel = _paged_extend_kernel
        kv_operands = (k_pages, v_pages)
        if body == "page":
            # a page as its [PS*K, D] rows: the same bytes on the chip, a
            # bitcast (paged_flash_decode says why)
            kv_spec = pl.BlockSpec((None, 1, ps * num_kv, d),
                                   _extend_page_rows_map,
                                   memory_space=pltpu.VMEM)
            kv_operands = tuple(x.reshape(layers, pool_pages, ps * num_kv, d)
                                for x in kv_operands)
        kv_specs = [kv_spec, kv_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, pl.cdiv(t, blk_q), block_tables.shape[1]),
        in_specs=[q_spec, *kv_specs],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((*rows, 1), jnp.float32),
            pltpu.VMEM((*rows, 1), jnp.float32),
            pltpu.VMEM((*rows, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, body=body, block_q=blk_q, block_k=ps,
                          num_kv=num_kv, groups=g, scale=d**-0.5,
                          block=block),
        out_shape=jax.ShapeDtypeStruct(q_shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(_layer_operand(layer), block_tables.astype(jnp.int32),
      start_pos.astype(jnp.int32), chunk_lens.astype(jnp.int32),
      q.reshape(q_shape), *kv_operands)
    return out.reshape(b, t, h, d)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret", "block"))
def paged_flash_extend(
    q: jnp.ndarray,  # [B, T, H, D] — chunk of queries
    k_pages: jnp.ndarray,  # [L, P, PS, K, D] — global page pool, all layers
    v_pages: jnp.ndarray,  # [L, P, PS, K, D]
    layer,  # int32 scalar — the layer of the pool to attend over
    block_tables: jnp.ndarray,  # [B, PPN] int32
    start_pos: jnp.ndarray,  # [B] int32 — global position of the first query
    chunk_lens: jnp.ndarray,  # [B] int32 — valid queries (rest are padding)
    *,
    block_q: int = EXTEND_BLOCK_Q,
    interpret: bool | None = None,
    block: int = 1,
) -> jnp.ndarray:
    """Paged chunked-prefill attention: T contiguous queries starting at
    global position start_pos[b] attend causally (block-causally with
    `block` > 1, ops/attention._block_end) over row b's pages (earlier
    chunks + this chunk), gathered through the prefetched block table by the
    KV BlockSpec index_map. KV blocks entirely in the future of the chunk
    skip their FLOPs (`pl.when` in _extend_item), so cost scales with the
    context actually filled, not pool capacity. The pool arrives STACKED
    with the layer index beside it, paged_flash_decode's contract: read in
    place at (layer, page), never `pool[layer]` (a slice handed to a
    pallas_call is copied whole), and `layer` is a run-time operand, so the
    layers of a scanned extend program are one kernel. What a grid step
    does with its page follows the q block's size (`extend_body`): a block
    pass's or a verify chunk's few queries take the page as it is stored,
    in one masked product; a prefill chunk's 128 take it a KV head at a
    time. Returns [B, T, H, D]."""
    return _paged_extend_call(
        q, k_pages, v_pages, None, layer, block_tables, start_pos,
        chunk_lens, block_q=block_q, interpret=interpret, block=block)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret", "block"))
def paged_flash_extend_quant(
    q: jnp.ndarray,  # [B, T, H, D] — chunk of queries
    k_pages: jnp.ndarray,  # [L, P, PS, K, D] int8 — all layers
    k_scales: jnp.ndarray,  # [P, PS, K] f32 — THE LAYER'S
    v_pages: jnp.ndarray,  # [L, P, PS, K, D] int8
    v_scales: jnp.ndarray,  # [P, PS, K] f32
    layer,  # int32 scalar — the layer of the value pools to attend over
    block_tables: jnp.ndarray,  # [B, PPN] int32
    start_pos: jnp.ndarray,  # [B] int32
    chunk_lens: jnp.ndarray,  # [B] int32
    *,
    block_q: int = EXTEND_BLOCK_Q,
    interpret: bool | None = None,
    block: int = 1,
) -> jnp.ndarray:
    """Int8 variant of paged_flash_extend: each page's vectors dequantize
    in VMEM. Same causal/ragged skip logic, garbage contract and choice of
    body. The values are read in place at (layer, page); the scales arrive
    as the layer's slice and gather through the same prefetched block table
    (paged_flash_decode_quant says why)."""
    return _paged_extend_call(
        q, k_pages, v_pages, (k_scales, v_scales), layer, block_tables,
        start_pos, chunk_lens, block_q=block_q, interpret=interpret,
        block=block)
