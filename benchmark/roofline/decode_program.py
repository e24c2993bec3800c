"""Operations and bytes one step of the burst decode program must do, from
shapes: every weight a token's forward pass reads (all of them but the
embedding table, of which a step reads one row per sequence; for a sparse
mixture, every expert — a batch of 32 tokens at top-2 of 8 reaches them all)
and the keys and values of the context alive, once per step."""

from __future__ import annotations


def work(config: dict, engine: dict, *, live_tokens: float, rows: float) -> dict:
    hf = config
    itemsize = 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4
    hidden, vocab = hf["hidden_size"], hf["vocab_size"]
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    head_dim = hf.get("head_dim") or hidden // heads
    layers = hf["num_hidden_layers"]
    embed_bytes = vocab * hidden * itemsize
    weight_bytes = engine["param_bytes"] - (
        0 if hf.get("tie_word_embeddings") else embed_bytes)
    kv_bytes = live_tokens * layers * kv_heads * head_dim * 2 * itemsize
    # operations: 2 per parameter a token's forward pass multiplies by (for a
    # mixture, the experts per token of the experts it is routed among: their
    # number under either key the published configs use, as the program's own
    # `from_hf_config` reads it, their width `moe_intermediate_size` where a
    # config states one), plus attention over the live context
    experts = hf.get("num_local_experts", hf.get("num_experts", 0))
    n_params = engine["n_params"] - vocab * hidden * (
        0 if hf.get("tie_word_embeddings") else 1)
    if experts:
        expert_params = layers * experts * 3 * hidden * hf.get(
            "moe_intermediate_size", hf["intermediate_size"])
        n_params -= expert_params * (1 - hf["num_experts_per_tok"] / experts)
    flops = 2 * n_params * rows + 4 * live_tokens * layers * heads * head_dim
    return {"flops": flops, "bytes": weight_bytes + kv_bytes}
