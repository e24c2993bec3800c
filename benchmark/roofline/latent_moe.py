"""Operations and bytes of a latent-attention mixture of experts
(`models/deepseek_v3.py`), from the configuration's shapes and the program's
own expert-load counters, and the names its two kernels carry in a device
trace. Three accounts:

  latent_decode_call   one call of `paged_latent_decode`: one layer's absorbed
                       attention of one new token per sequence. It must read
                       each live token's latent and shared rope key once
                       (kv_lora_rank + qk_rope_head_dim numbers; the queries
                       and the output beside them) and multiply-add per head
                       over the latent and rope numbers for the score and
                       over the latent for the mix.
  routed_experts       the grouped expert products of a set of step records:
                       the three matrices of every expert the records'
                       `experts_touched` counted, read once, and a
                       multiply-add per assignment and matrix element. The
                       shared experts are plain matmuls outside these
                       kernels and outside this account.
  decode_step          one step of the burst decode program: every weight
                       but the embedding table (a step reads one row of it
                       per sequence), the routed experts counted as touched
                       and not as held, plus the latent cache alive.
"""

from __future__ import annotations

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
LATENT_DECODE_OPS = ["paged_latent_decode"]
# The program's own grouped matmul (ops/pallas_moe.py), and where it falls
# back to `jax.lax.ragged_dot` what XLA lowers that to on a TPU: a Mosaic
# grouped matmul and a small kernel that lays out its work-list.
ROUTED_EXPERT_OPS = ["grouped_expert_matmul", "ragged-dot-none",
                     "ragged-dot-metadata"]


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def expert_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0)


def expert_bytes(hf: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"] * _itemsize(hf)


def latent_decode_call(hf: dict, *, live_tokens: float, rows: float) -> dict:
    heads = hf["num_attention_heads"]
    latent, rope = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    cache = live_tokens * (latent + rope) * _itemsize(hf)
    queries_and_out = rows * heads * (2 * latent + rope) * _itemsize(hf)
    return {"flops": 2 * live_tokens * heads * (2 * latent + rope),
            "bytes": cache + queries_and_out}


def routed_experts(hf: dict, *, experts_touched: float,
                   assignments: float) -> dict:
    per_assignment = 3 * 2 * hf["hidden_size"] * hf["moe_intermediate_size"]
    rows = assignments * (2 * hf["hidden_size"]
                          + 3 * hf["moe_intermediate_size"]) * _itemsize(hf)
    return {"flops": assignments * per_assignment,
            "bytes": experts_touched * expert_bytes(hf) + rows}


def decode_step(hf: dict, engine: dict, *, live_tokens: float, rows: float,
                experts_touched: float) -> dict:
    """`experts_touched`: distinct routed experts a step reads, summed over
    the expert layers."""
    itemsize = _itemsize(hf)
    hidden, vocab = hf["hidden_size"], hf["vocab_size"]
    embed = vocab * hidden * (0 if hf.get("tie_word_embeddings") else 1)
    held = expert_layers(hf) * hf["n_routed_experts"]
    weights = (engine["param_bytes"] - embed * itemsize
               - (held - experts_touched) * expert_bytes(hf))
    cache = (live_tokens * hf["num_hidden_layers"]
             * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * itemsize)
    per_expert = 3 * hidden * hf["moe_intermediate_size"]
    active = (engine["n_params"] - embed - held * per_expert
              + expert_layers(hf) * hf["num_experts_per_tok"] * per_expert)
    attention = latent_decode_call(hf, live_tokens=live_tokens, rows=rows)
    return {"flops": 2 * active * rows
            + hf["num_hidden_layers"] * attention["flops"],
            "bytes": weights + cache}
