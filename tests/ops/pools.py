"""KV page pools for the attention tests."""

import jax.numpy as jnp
import numpy as np

from llmlb_tpu.ops.pallas_attention import decode_work_list


def grouped_work(group, tables, lens, page_size, pages=None, kv_from=None):
    """The `work=` that makes a paged decode kernel take `group` pages a
    grid step, whatever its shapes' own group is; None (the kernel builds
    its list by `decode_group`) where `group` is None."""
    if group is None:
        return None
    return decode_work_list(tables, lens, page_size=page_size, pages=pages,
                            kv_from=kv_from, group=group)


def stacked_pool(pool, layer, num_layers=3):
    """The paged decode paths' operand: `pool` ([P, PS, K, D] values, [P, PS,
    K] scales, or a quantized {"q", "s"} pair of them) as layer `layer` of a
    pool stacked over layers. Every other layer is poison — NaN values,
    saturated int8 values, 1e30 scales — so reading the wrong layer cannot
    pass a parity check."""
    if isinstance(pool, dict):
        return {name: stacked_pool(member, layer, num_layers)
                for name, member in pool.items()}
    if pool.dtype == jnp.int8:
        poison = 127
    else:
        poison = 1e30 if pool.ndim == 3 else jnp.nan
    layers = [jnp.full_like(pool, poison)] * num_layers
    layers[layer] = pool
    return jnp.stack(layers)


# (KV heads, queries a KV head) the decode kernels' one masked product is
# held to: the benchmark's dense cells (Mistral-7B's, Nemotron-3-Nano's) and
# the two ends, ungrouped and one KV head
HEAD_SHAPES = {"K8xG4": (8, 4), "K2xG16": (2, 16), "MHA-8x1": (8, 1),
               "MQA-1x4": (1, 4)}

# Lengths of the paged decode kernels' contract cases, at page size PS = 16
# and tables PPN = 4 pages wide: name -> (kv_lens, pages). A length of 0 is
# a row that is not live.
DECODE_PS, DECODE_PPN = 16, 4
DECODE_CASES = {
    # ragged rows with dead rows between, before and after them
    "dead_rows_interleaved": ([0, 37, 0, 5, 0, 0, 64, 0], None),
    # on a page boundary, one cell under it and one over it
    "page_boundaries": ([16, 15, 17, 0, 32, 31, 33, 48], None),
    # a row's last page holds ONE live cell: each head finds its one column
    # there among the masked ones (its other heads', and the cells beyond)
    "one_cell_in_last_page": ([49, 0, 17, 1], None),
    "every_row_dead": ([0, 0, 0], None),
    # one live row at capacity, the table's width swept …
    "capacity_at_table_width": ([0, 64, 0], None),
    # … and fewer pages than its length needs: it attends over those alone
    "capacity_below_table_width": ([0, 64, 0, 20], 2),
}


def live_pages_case(rng, kv_lens, pages=None, ps=DECODE_PS, ppn=DECODE_PPN):
    """Block tables for `kv_lens` as the engine keeps them, and which pool
    pages a decode step may read. A live row holds distinct scattered pages
    for its length and the trash page (0) in the tail of its table; rows
    that are not live alternate between a zeroed table (freed, never used)
    and a table of real pages of their own (prefilling). Returns (tables
    [B, PPN] int32, readable [P] bool): readable are the pages a live row
    attends over within `pages` — not the trash page, not a dead row's
    pages, not a live row's pages beyond the sweep."""
    b = len(kv_lens)
    sweep = ppn if pages is None else pages
    num_pages = 1 + b * ppn
    free = list(rng.permutation(np.arange(1, num_pages)))
    tables = np.zeros((b, ppn), np.int32)
    readable = np.zeros((num_pages,), bool)
    dead_seen = 0
    for row, n in enumerate(kv_lens):
        if n == 0:
            dead_seen += 1
            held = ppn if dead_seen % 2 == 0 else 0
        else:
            held = -(-n // ps)
        for i in range(held):
            tables[row, i] = free.pop()
            readable[tables[row, i]] = n > 0 and i < sweep
    return jnp.asarray(tables), readable
