"""Operations and bytes of a decoder whose FULL layers are latent attention
over the cells a learned indexer picks (an index key a token beside the
latent and the rope cell, 2,048 chosen of the live context) and whose
SLIDING layers are latent attention of other sizes over a ring a slot, under
one dense feed-forward and then a mixture a chip holds a share of, with a
shared expert (`models/dots3_note.py`), from the configuration's shapes — by
the names its own `config.json` gives them: `layer_types`, `index_*`,
`kv_lora_rank`, `swa_*`, `n_routed_experts` — and the program's own
counters, and the names its kernels carry in a device trace.
`n_routed_experts` are the experts THIS CHIP holds of the
`expert_parallel.experts` the router scores. Each account is of the WORK the
MECHANISM needs, whatever implements it and however it is stored: a query's
attention reads the cells it CHOSE (`index_selected_cells`), so a kernel
that reads the whole live context under the selection's mask shows as a LOW
share, never as one above 100; the shared key's 64 numbers count as 64
though their cell is 128 lanes wide, and a ring's 513 cells as 513 though
640 are stored. Five accounts:

  index_select   the indexer's scores over `cells` (query, cell) pairs of
                 `rows` (sequence, full layer) pairs: each cell's index key
                 read once for all index heads, the queries and their
                 weights beside it; a multiply-add a head, cell and key
                 number, and the ReLU, the weight and the sum a head and
                 cell. The top-k's search moves nothing through memory
                 that the scores did not (one float a cell) and is counted
                 with them: 4 more bytes a cell.
  sparse_decode  the full layers' decode attention over the CHOSEN cells:
                 each cell's latent and shared key read once for all heads
                 (roofline/latent_moe.py's account at the cells chosen).
  window_decode  the sliding layers' decode attention over the ring cells in
                 use, at the `swa_*` sizes.
  held_experts   the grouped expert products of a set of step records
                 (roofline/band_moe.py's account at this configuration's
                 keys): the THREE matrices of every held expert the records'
                 `experts_touched` counted, read once, and a multiply-add
                 per held assignment and matrix element.
  decode_step    one step of the burst decode program: every weight but the
                 embedding table (a step reads one row of it a sequence),
                 the held experts counted as touched and not as held, and
                 the three attentions' accounts above.
"""

from __future__ import annotations

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
INDEX_OPS = ["index_scores_decode"]
SPARSE_DECODE_OPS = ["sparse_latent_decode"]
WINDOW_DECODE_OPS = ["window_latent_decode"]
ROUTED_EXPERT_OPS = ["grouped_expert_matmul", "ragged-dot-none",
                     "ragged-dot-metadata"]
FULL, SLIDING = "full_attention", "sliding_attention"  # `layer_types`


def is_sparse(hf: dict) -> bool:
    """Whether a configuration is of this family: the readers of this
    family's metrics report nothing for any other."""
    return hf.get("model_type") == "dots3_note"


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def layers(hf: dict, kind: str) -> int:
    return list(hf["layer_types"]).count(kind)


def moe_layers(hf: dict) -> int:
    return max(0, hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0))


def expert_params(hf: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict) -> int:
    return expert_params(hf) * _itemsize(hf)


def held_slots(hf: dict) -> int:
    """Expert slots a step could touch here: mixture layers x experts held."""
    return moe_layers(hf) * hf["n_routed_experts"]


def index_select(hf: dict, *, cells: float, rows: float) -> dict:
    """`cells`: (query, cell) pairs scored, summed over the full layers;
    `rows`: (sequence, full layer) pairs."""
    heads, width = hf["index_n_heads"], hf["index_head_dim"]
    return {"flops": cells * heads * (2 * width + 3),
            "bytes": (cells * width + rows * heads * (width + 1))
            * _itemsize(hf) + 4 * cells}


def _latent_decode(hf: dict, pre: str, heads: int, *, cells: float,
                   rows: float) -> dict:
    latent, rope = hf[pre + "kv_lora_rank"], hf[pre + "qk_rope_head_dim"]
    return {"flops": 2 * cells * heads * (2 * latent + rope),
            "bytes": (cells * (latent + rope)
                      + rows * heads * (2 * latent + rope)) * _itemsize(hf)}


def sparse_decode(hf: dict, *, cells: float, rows: float) -> dict:
    """`cells`: (query, CHOSEN cell) pairs, summed over the full layers."""
    return _latent_decode(hf, "", hf["num_attention_heads"], cells=cells,
                          rows=rows)


def window_decode(hf: dict, *, cells: float, rows: float) -> dict:
    """`cells`: ring cells in use, summed over rows and sliding layers."""
    return _latent_decode(hf, "swa_", hf["swa_num_attention_heads"],
                          cells=cells, rows=rows)


def held_experts(hf: dict, *, experts_touched: float,
                 assignments: float) -> dict:
    rows = assignments * (2 * hf["hidden_size"]
                          + 3 * hf["moe_intermediate_size"]) * _itemsize(hf)
    return {"flops": assignments * 2 * expert_params(hf),
            "bytes": experts_touched * expert_bytes(hf) + rows}


def decode_step(hf: dict, engine: dict, *, scored_cells: float,
                selected_cells: float, window_cells: float, rows: float,
                experts_touched: float) -> dict:
    """`scored_cells`, `selected_cells`, `window_cells`: the (layer, cell)
    pairs a step's indexers scored, its full layers' attentions chose and
    its rings held (the records' counters a step); `rows`: sequences the
    step advances; `experts_touched`: distinct held experts a step reads,
    summed over the mixture layers."""
    itemsize = _itemsize(hf)
    embed = hf["vocab_size"] * hf["hidden_size"]
    n_f, n_s = layers(hf, FULL), layers(hf, SLIDING)
    weights = (engine["param_bytes"] - embed * itemsize
               - (held_slots(hf) - experts_touched) * expert_bytes(hf))
    parts = [index_select(hf, cells=scored_cells, rows=rows * n_f),
             sparse_decode(hf, cells=selected_cells, rows=rows * n_f),
             window_decode(hf, cells=window_cells, rows=rows * n_s)]
    share = hf["n_routed_experts"] / (hf.get("expert_parallel") or {}).get(
        "experts", hf["n_routed_experts"])
    active = (engine["n_params"] - embed - held_slots(hf) * expert_params(hf)
              + moe_layers(hf) * hf["num_experts_per_tok"] * share
              * expert_params(hf))
    return {"flops": 2 * active * rows + sum(p["flops"] for p in parts),
            "bytes": weights + sum(p["bytes"] for p in parts)}
