"""Pallas TPU grouped matmul for a mixture's experts: rows sorted by expert
times the STACKED expert weights [L, X, K, N], read in place at (layer,
expert).

`jax.lax.ragged_dot` is the plain path (ops/moe.py) and what this is checked
against. On the chip it cost the decode step of a 128-expert layer twice
over (my chip runs, PR 31): its operand has to be one layer's experts, and a
slice `w[layer]` of the stack handed to a kernel is copied whole on every
call (1.2 GB a layer for the three matrices, 2.5 s of 4.3 s of device time);
and its grid visits every group, so handing it the stack as L x X groups to
avoid the slice made a step four times slower (and tripped an XLA bitcast
check at one batch size). Here:

- The grid is a WORK-LIST of (expert, row tile) items, experts in order and
  an expert's row tiles in order, of run-time length (`group_work_list`,
  built once per routed layer and shared by its three products): an expert
  no token chose has no item and its weights are never read; an expert with
  a handful of rows (decode) costs one tile.
- The weights' BlockSpec index map picks (layer, expert) through scalar
  prefetch: the stack is an operand whole and is read in place. `layer` is
  a run-time value, so the layers of an unrolled decode program and the
  iterations of a prefill scan share one kernel.
- An item computes its whole row tile against its expert's matrix and
  writes only the rows that are the expert's (a mask by row range); a tile
  that several experts share is visited by consecutive items, the output
  block staying in VMEM between them. Rows that belong to no expert
  (padding, sorted last) may lie in tiles no item visits: what comes out
  for them is unspecified, and the caller masks it.

The design is the public "megablox" grouped matmul's (PAPERS.md), cut to
what serving needs: no transposes, no backward, the contraction whole.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


class GroupWork(NamedTuple):
    """The grouped matmul's grid: one item per (expert, row tile) pair that
    holds rows."""

    count: jnp.ndarray  # [] int32 — items in use: the grid's run-time length
    expert_of: jnp.ndarray  # [W] int32 — the expert whose matrix item i reads
    tile_of: jnp.ndarray  # [W] int32 — the row tile it computes
    row_lo: jnp.ndarray  # [W] int32 — its rows of the sorted matrix, [lo, hi)
    row_hi: jnp.ndarray  # [W] int32
    first: jnp.ndarray  # [W] int32 — 1 where it is the first item of its tile


# Rows an item computes at once, whatever its expert's share of them: a
# quarter of an MXU pass, sized for a decode step's few rows per expert. An
# expert's consecutive row tiles find its matrix still in VMEM. A prefill
# long enough to fill 128-row tiles is in no cell and was never measured: a
# second size waits for the chip run that picks it (PERF.md section 7).
ROW_TILE = 32


def group_work_list(load: jnp.ndarray, *, rows: int, tile: int) -> GroupWork:
    """The work-list of one routed layer: `load` [X] rows per expert (the
    sorted matrix holds expert 0's rows first), `rows` the matrix's padded
    height, a multiple of `tile`. W = X + rows / tile is static: an expert
    adds one item, and one more for each tile boundary its rows cross."""
    x = load.shape[0]
    tiles = rows // tile
    load = load.astype(jnp.int32)
    end_row = jnp.cumsum(load)
    start_row = end_row - load
    first_tile = start_row // tile
    per_expert = jnp.where(load > 0,
                           (end_row - 1) // tile - first_tile + 1, 0)
    end = jnp.cumsum(per_expert)
    item = jnp.arange(x + tiles, dtype=jnp.int32)
    ended = item[:, None] >= end[None, :]  # [W, X]: experts before item i's
    expert_of = jnp.minimum(jnp.sum(ended, axis=1, dtype=jnp.int32), x - 1)
    before = jnp.sum(jnp.where(ended, per_expert[None, :], 0), axis=1)
    tile_of = jnp.clip(first_tile[expert_of] + item - before, 0, tiles - 1)
    row_lo = jnp.maximum(start_row[expert_of], tile_of * tile)
    row_hi = jnp.minimum(end_row[expert_of], (tile_of + 1) * tile)
    first = (tile_of != jnp.roll(tile_of, 1)) | (item == 0)
    return GroupWork(end[-1], expert_of, tile_of, row_lo, row_hi,
                     first.astype(jnp.int32))


def _column_tile(k: int, n: int, itemsize: int, budget: int = 4 << 20) -> int:
    """Columns of the weight block: all of them where a [K, N] matrix fits
    the budget (one contiguous read per expert), else the widest multiple of
    128 dividing N that does."""
    if k * n * itemsize <= budget or n % 128:
        return n
    best = 128
    for cols in range(128, n, 128):
        if n % cols == 0 and k * cols * itemsize <= budget:
            best = cols
    return best


def _gmm_kernel(layer_ref, count_ref, expert_ref, tile_ref, lo_ref, hi_ref,
                first_ref, x_ref, w_ref, o_ref, *, tile: int,
                transposed: bool):
    del layer_ref, expert_ref
    item = pl.program_id(0) % count_ref[0]
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [TM, TN]
    row = tile_ref[item] * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, 1), dimension=0)
    mine = (row >= lo_ref[item]) & (row < hi_ref[item])

    @pl.when(first_ref[item] == 1)
    def _start():  # rows of the tile no later item writes come out as zeros
        o_ref[...] = jnp.where(mine, acc, 0.0).astype(o_ref.dtype)

    @pl.when(first_ref[item] == 0)
    def _join():
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype", "interpret",
                                             "transposed"))
def grouped_expert_matmul(
    rows: jnp.ndarray,  # [N, K] sorted by expert, N a multiple of `tile`
    w: jnp.ndarray,  # [L, X, K, O] — every layer's experts
    layer,  # int32 scalar — the layer whose experts multiply
    work: GroupWork,  # group_work_list of the layer's load, N and `tile`
    *,
    tile: int,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
    transposed: bool = False,
) -> jnp.ndarray:
    """rows[i] @ w[layer, expert of row i] -> [N, O], row by row through its
    own expert's matrix; rows beyond the experts' (padding) unspecified.

    `transposed`: w is [L, X, O, K], as a checkpoint stores a Linear, and
    the product contracts the last axis of both. For an O that is no
    multiple of 128 lanes (1,856) it is the layout to store: the chip lays
    a [K, O] matrix of such a width out with K minor, and handing that to a
    kernel copied the whole stack, 3.8 GB, on every call (PERF.md section
    6, PR 38)."""
    if interpret is None:
        interpret = _interpret_default()
    n, k = rows.shape
    o = w.shape[-2] if transposed else w.shape[-1]
    cols = _column_tile(k, o, w.dtype.itemsize)
    col_tiles = o // cols

    # grid step i: column tile i // count (outer), item i % count (inner) —
    # a row tile's consecutive items keep its output block in VMEM
    def x_map(i, layer, count, expert_of, tile_of, lo, hi, first):
        return (tile_of[i % count[0]], 0)

    def w_map(i, layer, count, expert_of, tile_of, lo, hi, first):
        at = (0, i // count[0])
        return (layer[0], expert_of[i % count[0]], *(at[::-1] if transposed
                                                     else at))

    def o_map(i, layer, count, expert_of, tile_of, lo, hi, first):
        return (tile_of[i % count[0]], i // count[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(work.count * col_tiles,),
        in_specs=[
            pl.BlockSpec((tile, k), x_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((None, None, cols, k) if transposed
                         else (None, None, k, cols), w_map,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, cols), o_map, memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tile=tile, transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((n, o), out_dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="grouped_expert_matmul",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.maximum(work.count, 1).reshape(1), work.expert_of, work.tile_of,
      work.row_lo, work.row_hi, work.first, rows, w)
