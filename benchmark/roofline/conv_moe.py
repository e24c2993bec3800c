"""Operations and bytes of a decoder whose layers mix their tokens by a GATED
SHORT CONVOLUTION (two carried rows a slot and layer) or by attention over
pages, under dense feed-forwards and then a mixture held whole, the head
tied to the embedding table (`models/lfm2_moe.py`), from the configuration's
shapes — by the names its own `config.json` gives them: `layer_types`,
`conv_L_cache`, `num_dense_layers`, `num_experts`, `moe_intermediate_size` —
and the program's own counters, and the names its kernels carry in a device
trace. Each account is of the WORK the equations need, whatever implements
it and however it is stored, and counts live TOKENS and TOUCHED experts,
never a pool's capacity, a page's unread cells or all the experts: a kernel
that moves more shows as a LOWER share, never a higher one. Three accounts:

  experts_call      ONE of a mixture layer's three grouped products (gate,
                    up, down: one call of the kernel each): one matrix
                    [2048, 1536] of every expert the layer touched read
                    once, the assignments' rows in and out beside it; a
                    multiply-add per assignment and matrix element.
  attn_decode_call  the attention layers' decode attention over `cells` live
                    (token, layer) pairs: each cell's K and V of every KV
                    head read once, by the equations' heads (8 of 64: 2,048
                    B a cell) whatever the pool stores; a multiply-add per
                    query head, cell and channel, twice.
  decode_step       one step of the burst decode program: every weight
                    outside the experts, the embedding table among them once
                    (it is the head: a step reads it whole, and the rows it
                    reads as an embedding are nothing beside that), the
                    experts AS TOUCHED, the carried rows read and written
                    for the (row, layer) pairs moved, and the keys and
                    values alive in the attention layers. `conv_bytes` is the
                    conv mixers' part: their weights and their rows.
"""

from __future__ import annotations

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
# ops/pallas_moe.py's grouped matmul, and what `jax.lax.ragged_dot` lowers to
# on a TPU where the program falls back to it.
EXPERT_OPS = ["grouped_expert_matmul", "ragged-dot-none",
              "ragged-dot-metadata"]
ATTN_DECODE_OPS = ["paged_flash_decode"]
CONV, ATTENTION = "conv", "full_attention"  # `layer_types`
PRODUCTS = 3  # grouped products a mixture layer: gate, up, down


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def layers(hf: dict, kind: str) -> int:
    return list(hf["layer_types"]).count(kind)


def moe_layers(hf: dict) -> int:
    return max(0, hf["num_hidden_layers"] - hf.get("num_dense_layers", 0))


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def matrix_params(hf: dict) -> int:
    """One of a routed expert's three matrices."""
    return hf["hidden_size"] * hf["moe_intermediate_size"]


def expert_slots(hf: dict) -> int:
    """Expert slots a step could touch: mixture layers x experts."""
    return moe_layers(hf) * hf["num_experts"]


def conv_mixer_params(hf: dict) -> int:
    """One conv mixer: its norm, the projection to [B | C | u], the taps,
    the projection back."""
    e = hf["hidden_size"]
    return e + 3 * e * e + hf.get("conv_L_cache", 3) * e + e * e


def carried_bytes(hf: dict) -> int:
    """One (row, layer)'s carried rows, read once and written once."""
    return (2 * (hf.get("conv_L_cache", 3) - 1) * hf["hidden_size"]
            * _itemsize(hf))


def experts_call(hf: dict, *, experts_touched: float,
                 assignments: float) -> dict:
    """`experts_touched`, `assignments`: of ONE mixture layer."""
    rows = assignments * (hf["hidden_size"] + hf["moe_intermediate_size"]
                          ) * _itemsize(hf)
    return {"flops": 2 * assignments * matrix_params(hf),
            "bytes": experts_touched * matrix_params(hf) * _itemsize(hf)
            + rows}


def attn_decode_call(hf: dict, *, cells: float, rows: float) -> dict:
    """`cells`: live (token, layer) pairs; `rows`: (sequence, layer) pairs."""
    heads = hf["num_attention_heads"]
    d, itemsize = head_dim(hf), _itemsize(hf)
    return {"flops": 4 * cells * heads * d,
            "bytes": (cells * 2 * hf["num_key_value_heads"] * d
                      + rows * 2 * heads * d) * itemsize}


def decode_step(hf: dict, engine: dict, *, rows: float, conv_rows: float,
                live_cells: float, experts_touched: float) -> dict:
    """`rows`: sequences the step advances; `conv_rows`: (row, layer) pairs
    whose carried rows it moves; `live_cells`: (token, layer) pairs its
    attentions read; `experts_touched`: distinct experts it reads, summed
    over the mixture layers."""
    itemsize = _itemsize(hf)
    expert = PRODUCTS * matrix_params(hf)
    weights = (engine["param_bytes"]
               - (expert_slots(hf) - experts_touched) * expert * itemsize)
    carried = conv_rows * carried_bytes(hf)
    attention = attn_decode_call(hf, cells=live_cells,
                                 rows=rows * layers(hf, ATTENTION))
    active = (engine["n_params"] - expert_slots(hf) * expert
              + moe_layers(hf) * hf["num_experts_per_tok"] * expert)
    return {"flops": 2 * active * rows + attention["flops"],
            "bytes": weights + carried + attention["bytes"],
            "conv_bytes": (layers(hf, CONV) * conv_mixer_params(hf) * itemsize
                           + carried)}
