"""Operations and bytes of a decoder whose WINDOW attention layers (a ring a
slot, a sink a head) alternate with GLOBAL ones (pages) under a dense
feed-forward or a mixture a chip holds a share of (`models/mimo_v2.py`),
from the configuration's shapes — by the names its own `config.json` gives
them: `hybrid_layer_pattern`, `moe_layer_freq`, `swa_num_key_value_heads`,
`v_head_dim`, `sliding_window` — and the program's own counters, and the
names its kernels carry in a device trace. Keys are `head_dim` wide and
values `v_head_dim`; `n_routed_experts` are the experts THIS CHIP holds of
the `expert_parallel.experts` the router scores. Each account is of the
WORK, whatever implements it, and counts live TOKENS and TOUCHED experts,
never a pool's capacity or all the held. Four accounts:

  window_decode   the window layers' decode attention over a set of step
                  records: the ring cells the records' `window_kv_tokens`
                  counted (a live row's min(len, window) in every window
                  layer), each cell's keys and values of all its KV heads
                  read once; 2 (192 + 128) operations a cell and query head.
  global_decode   the same of the global layers, over `global_kv_tokens` (a
                  live row's whole length in every global layer).
  held_experts    the grouped expert products of a set of step records: the
                  THREE matrices of every held expert the records'
                  `experts_touched` counted, read once, and a multiply-add
                  per held assignment and matrix element.
  decode_step     one step of the burst decode program: every weight but the
                  embedding table (a step reads one row of it a sequence),
                  the held experts counted as touched and not as held, plus
                  the global layers' live keys and values and the window
                  layers' live ring cells.
"""

from __future__ import annotations

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
WINDOW_DECODE_OPS = ["paged_window_decode"]
GLOBAL_DECODE_OPS = ["paged_flat_decode"]
# ops/pallas_moe.py's grouped matmul, and what `jax.lax.ragged_dot` lowers to
# on a TPU where the program falls back to it.
ROUTED_EXPERT_OPS = ["grouped_expert_matmul", "ragged-dot-none",
                     "ragged-dot-metadata"]
WINDOW, GLOBAL = 1, 0  # `hybrid_layer_pattern`'s kinds


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def layers(hf: dict, kind: int) -> int:
    return list(hf["hybrid_layer_pattern"]).count(kind)


def moe_layers(hf: dict) -> int:
    return sum(hf["moe_layer_freq"])


def cell_numbers(hf: dict, kind: int) -> int:
    """Numbers one token leaves in one layer of a kind: a key and a value
    on every KV head of the kind."""
    heads = (hf["swa_num_key_value_heads"] if kind == WINDOW
             else hf["num_key_value_heads"])
    return heads * (hf["head_dim"] + hf["v_head_dim"])


def expert_params(hf: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict) -> int:
    return expert_params(hf) * _itemsize(hf)


def held_slots(hf: dict) -> int:
    """Expert slots a step could touch here: mixture layers x experts held."""
    return moe_layers(hf) * hf["n_routed_experts"]


def _attention(hf: dict, kind: int, cells: float) -> dict:
    """`cells`: (layer, live token) pairs read, summed over the kind's
    layers."""
    return {"flops": (2 * cells * hf["num_attention_heads"]
                      * (hf["head_dim"] + hf["v_head_dim"])),
            "bytes": cells * cell_numbers(hf, kind) * _itemsize(hf)}


def window_decode(hf: dict, *, cells: float) -> dict:
    return _attention(hf, WINDOW, cells)


def global_decode(hf: dict, *, cells: float) -> dict:
    return _attention(hf, GLOBAL, cells)


def held_experts(hf: dict, *, experts_touched: float,
                 assignments: float) -> dict:
    rows = assignments * (2 * hf["hidden_size"]
                          + 3 * hf["moe_intermediate_size"]) * _itemsize(hf)
    return {"flops": assignments * 2 * expert_params(hf),
            "bytes": experts_touched * expert_bytes(hf) + rows}


def decode_step(hf: dict, engine: dict, *, live_tokens: float, rows: float,
                experts_touched: float) -> dict:
    """`experts_touched`: distinct held experts a step reads, summed over
    the mixture layers. `live_tokens`, `rows`: context alive and sequences
    decoding; a row's ring holds min(its length, the window) cells."""
    itemsize = _itemsize(hf)
    embed = hf["vocab_size"] * hf["hidden_size"]
    weights = (engine["param_bytes"] - embed * itemsize
               - (held_slots(hf) - experts_touched) * expert_bytes(hf))
    ring = min(live_tokens, rows * hf["sliding_window"]) * layers(hf, WINDOW)
    pages = live_tokens * layers(hf, GLOBAL)
    window, whole = window_decode(hf, cells=ring), global_decode(hf, cells=pages)
    share = hf["n_routed_experts"] / (hf.get("expert_parallel") or {}).get(
        "experts", hf["n_routed_experts"])
    active = (engine["n_params"] - embed - held_slots(hf) * expert_params(hf)
              + moe_layers(hf) * hf["num_experts_per_tok"] * share
              * expert_params(hf))
    return {"flops": 2 * active * rows + window["flops"] + whole["flops"],
            "bytes": weights + window["bytes"] + whole["bytes"]}
