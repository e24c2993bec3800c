"""Operations and bytes of a decoder whose WINDOW attention layers read the
last `sliding_window` positions (held as a band of pages a slot) beside
GLOBAL ones that read the whole context (pages), every sub-layer normed on
both sides, under dense feed-forwards and then a mixture a chip holds a
share of, with a shared expert (`models/afmoe.py`), from the configuration's
shapes — by the names its own `config.json` gives them: `layer_types`,
`sliding_window`, `num_dense_layers`, `num_experts`, `moe_intermediate_size`
— and the program's own counters, and the names its kernels carry in a
device trace. `num_experts` are the experts THIS CHIP holds of the
`expert_parallel.experts` the router scores. Each account is of the WORK,
whatever implements it, and counts live TOKENS and TOUCHED experts, never a
pool's capacity, a page's unread cells or all the held. Three accounts:

  attention_decode  a kind's decode attention over a set of step records:
                  the cells a counter of the records counted (the window
                  layers' `window_kv_tokens`, a live row's min(len, window)
                  in every window layer; the global layers'
                  `global_kv_tokens`, its whole length in every global
                  one), each cell's keys and values of all its KV heads
                  read once; 4 x head_dim operations a cell and query head.
                  Both kinds have one geometry, so one account.
  held_experts    the grouped expert products of a set of step records: the
                  THREE matrices of every held expert the records'
                  `experts_touched` counted, read once, and a multiply-add
                  per held assignment and matrix element.
  decode_step     one step of the burst decode program: every weight but the
                  embedding table (a step reads one row of it a sequence),
                  the held experts counted as touched and not as held, plus
                  the cells both kinds of attention read.
"""

from __future__ import annotations

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
WINDOW_DECODE_OPS = ["paged_band_decode"]
GLOBAL_DECODE_OPS = ["paged_flash_decode"]
# ops/pallas_moe.py's grouped matmul, and what `jax.lax.ragged_dot` lowers to
# on a TPU where the program falls back to it.
ROUTED_EXPERT_OPS = ["grouped_expert_matmul", "ragged-dot-none",
                     "ragged-dot-metadata"]
WINDOW, GLOBAL = "sliding_attention", "full_attention"  # `layer_types`


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def layers(hf: dict, kind: str) -> int:
    return list(hf["layer_types"]).count(kind)


def moe_layers(hf: dict) -> int:
    return max(0, hf["num_hidden_layers"] - hf.get("num_dense_layers", 0))


def cell_numbers(hf: dict) -> int:
    """Numbers one token leaves in one attention layer: a key and a value on
    every KV head."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"]


def expert_params(hf: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict) -> int:
    return expert_params(hf) * _itemsize(hf)


def held_slots(hf: dict) -> int:
    """Expert slots a step could touch here: mixture layers x experts held."""
    return moe_layers(hf) * hf["num_experts"]


def attention_decode(hf: dict, *, cells: float) -> dict:
    """`cells`: (layer, live token) pairs read, summed over the layers."""
    return {"flops": 4 * cells * hf["num_attention_heads"] * hf["head_dim"],
            "bytes": cells * cell_numbers(hf) * _itemsize(hf)}


def held_experts(hf: dict, *, experts_touched: float,
                 assignments: float) -> dict:
    rows = assignments * (2 * hf["hidden_size"]
                          + 3 * hf["moe_intermediate_size"]) * _itemsize(hf)
    return {"flops": assignments * 2 * expert_params(hf),
            "bytes": experts_touched * expert_bytes(hf) + rows}


def decode_step(hf: dict, engine: dict, *, window_cells: float,
                global_cells: float, rows: float,
                experts_touched: float) -> dict:
    """`window_cells`, `global_cells`: the (layer, live token) pairs a step's
    attentions of each kind read (the records' counters a step);
    `experts_touched`: distinct held experts a step reads, summed over the
    mixture layers; `rows`: sequences decoding."""
    itemsize = _itemsize(hf)
    embed = hf["vocab_size"] * hf["hidden_size"]
    weights = (engine["param_bytes"] - embed * itemsize
               - (held_slots(hf) - experts_touched) * expert_bytes(hf))
    cells = attention_decode(hf, cells=window_cells + global_cells)
    share = hf["num_experts"] / (hf.get("expert_parallel") or {}).get(
        "experts", hf["num_experts"])
    active = (engine["n_params"] - embed - held_slots(hf) * expert_params(hf)
              + moe_layers(hf) * hf["num_experts_per_tok"] * share
              * expert_params(hf))
    return {"flops": 2 * active * rows + cells["flops"],
            "bytes": weights + cells["bytes"]}
