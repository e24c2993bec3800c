"""Operations and bytes one call of the `paged_flash_decode` kernel
(ops/pallas_attention.py) must do: one layer's attention of one new token
per sequence over that sequence's live keys and values. It must read each
live key and value once (the query and the output are a rounding error
beside them) and do a multiply-add per query head, key and channel, twice
(scores, then values)."""

from __future__ import annotations


def work(config: dict, engine: dict, *, live_tokens: float, rows: float) -> dict:
    hf = config
    itemsize = 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    kv_bytes = live_tokens * kv_heads * head_dim * 2 * itemsize
    qo_bytes = rows * heads * head_dim * 2 * itemsize
    return {"flops": 4 * live_tokens * heads * head_dim,
            "bytes": kv_bytes + qo_bytes}
