"""Operations and bytes of a mixture of experts that generates by diffusion
over blocks (`models/sdar_moe.py`), from the configuration's shapes and the
program's own counters, and the names its kernels carry in a device trace.
A decode step of such a model is a BLOCK PASS: `block_length` positions a
row behind the row's committed cache. Three accounts:

  block_extend_call  one call of the paged extend kernel in a block pass:
                     one layer's attention of each row's block over that
                     row's live keys and values. It must read each live key
                     and value once (the blocks' queries and the output
                     beside them) and multiply-add per query head, block
                     position, key and channel, twice (scores, then values).
  routed_experts     the grouped expert products of a set of step records,
                     by `roofline/latent_moe.py`'s count: the three matrices
                     of every expert the records' `experts_touched` counted,
                     read once, and a multiply-add per assignment and matrix
                     element. The same kernel at the same expert shapes.
  block_pass         one pass of the burst program: every weight but the
                     embedding table (a pass reads `block_length` rows of it
                     per sequence), the routed experts counted as touched
                     and not as held, plus the keys and values alive.
"""

from __future__ import annotations

from benchmark import manifest

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
BLOCK_EXTEND_OPS = ["paged_flash_extend", "_paged_extend_kernel",
                    "paged_extend_kernel"]
ROUTED_EXPERT_OPS = ["grouped_expert_matmul", "ragged-dot-none",
                     "ragged-dot-metadata"]


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def block_length(hf: dict) -> int:
    return int(hf.get("block_length",
                      (hf.get("assumed") or {}).get("block_length", 4)))


def _heads(hf: dict) -> tuple[int, int, int]:
    heads = hf["num_attention_heads"]
    return (heads, hf.get("num_key_value_heads", heads),
            hf.get("head_dim") or hf["hidden_size"] // heads)


def expert_bytes(hf: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"] * _itemsize(hf)


def block_extend_call(hf: dict, *, live_tokens: float, rows: float) -> dict:
    heads, kv_heads, head_dim = _heads(hf)
    block = block_length(hf)
    kv = live_tokens * kv_heads * head_dim * 2 * _itemsize(hf)
    queries_and_out = rows * block * heads * head_dim * 2 * _itemsize(hf)
    return {"flops": 4 * live_tokens * block * heads * head_dim,
            "bytes": kv + queries_and_out}


def routed_experts(hf: dict, *, experts_touched: float,
                   assignments: float) -> dict:
    return manifest.load_module("roofline", "latent_moe").routed_experts(
        hf, experts_touched=experts_touched, assignments=assignments)


def block_pass(hf: dict, engine: dict, *, live_tokens: float, rows: float,
               experts_touched: float) -> dict:
    """`experts_touched`: distinct routed experts a pass reads, summed over
    the layers; `rows`: sequences decoding."""
    itemsize = _itemsize(hf)
    hidden, layers = hf["hidden_size"], hf["num_hidden_layers"]
    _, kv_heads, head_dim = _heads(hf)
    embed = hf["vocab_size"] * hidden
    held = layers * hf["num_experts"]
    weights = (engine["param_bytes"] - embed * itemsize
               - (held - experts_touched) * expert_bytes(hf))
    cache = live_tokens * layers * kv_heads * head_dim * 2 * itemsize
    per_expert = 3 * hidden * hf["moe_intermediate_size"]
    active = (engine["n_params"] - embed - held * per_expert
              + layers * hf["num_experts_per_tok"] * per_expert)
    attention = block_extend_call(hf, live_tokens=live_tokens, rows=rows)
    return {"flops": 2 * active * rows * block_length(hf)
            + layers * attention["flops"],
            "bytes": weights + cache}
