"""Operations and bytes of a hybrid of gated delta-rule (linear-attention)
and full-attention layers, each with a dense feed-forward
(`models/olmo_hybrid.py`), from the configuration's shapes and the program's
own counters, and the names its kernels carry in a device trace. Each
account is of the WORK the equations need, whatever implements it and
however it is stored: a state padded in memory or two dead KV heads in a
cell of the page pool move more bytes than are counted here, and show as a
LOWER share, never a higher one. Three accounts:

  step_call     one layer's rule step for `rows` sequences advanced by one
                token: each row's state (heads x key x value, float32) read
                once and written once, its inputs (q, k, v, the decay and
                the write strength, float32) and its output beside it; 7
                operations a state element (the decay; S^T k, the update and
                S^T q a multiply and an add each).
  attn_decode   the full-attention layers' decode attention over `cells`
                live (token, layer) pairs: each cell's K and V of every KV
                head read once; a multiply-add per query head, cell and
                channel, twice (benchmark/roofline/paged_flash_decode.py's
                account, at the cells the program counted).
  decode_step   one step of the burst decode program: every weight but the
                embedding table (a step reads one row of it a sequence),
                plus the state read and written for the rows advanced (the
                convolution's rows with it), plus the keys and values alive
                in the attention layers. `state_bytes` is the state's part.
"""

from __future__ import annotations

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
STEP_OPS = ["delta_rule_step"]
ATTN_DECODE_OPS = ["paged_flash_decode"]
STATE_ITEMSIZE = 4  # the rule's state is float32 whatever the weights are
LINEAR, FULL = "linear_attention", "full_attention"


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def layers(hf: dict, kind: str) -> int:
    """Layers of a kind (`linear_attention`, `full_attention`)."""
    return hf["layer_types"].count(kind)


def state_elements(hf: dict) -> int:
    """One sequence's state in one layer."""
    return (hf["linear_num_key_heads"] * hf["linear_key_head_dim"]
            * hf["linear_value_head_dim"])


def conv_channels(hf: dict) -> int:
    """Channels of [q | k | v], what the convolution runs over."""
    return hf["linear_num_key_heads"] * (2 * hf["linear_key_head_dim"]
                                         + hf["linear_value_head_dim"])


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def step_call(hf: dict, *, rows: float) -> dict:
    heads = hf["linear_num_key_heads"]
    vectors = (2 * heads * hf["linear_key_head_dim"]
               + 2 * heads * hf["linear_value_head_dim"]
               + 2 * heads) * STATE_ITEMSIZE
    return {"flops": 7 * rows * state_elements(hf),
            "bytes": rows * (2 * state_elements(hf) * STATE_ITEMSIZE
                             + vectors)}


def attn_decode(hf: dict, *, cells: float, rows: float) -> dict:
    """`cells`: live (token, layer) pairs; `rows`: (sequence, layer) pairs."""
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    d, itemsize = head_dim(hf), _itemsize(hf)
    return {"flops": 4 * cells * heads * d,
            "bytes": (cells * 2 * kv_heads * d
                      + rows * 2 * heads * d) * itemsize}


def decode_step(hf: dict, engine: dict, *, live_tokens: float,
                rows: float) -> dict:
    """`live_tokens`: tokens of context alive, summed over the sequences;
    `rows`: sequences the step advances."""
    itemsize = _itemsize(hf)
    embed = hf["vocab_size"] * hf["hidden_size"]
    n_l, n_a = layers(hf, LINEAR), layers(hf, FULL)
    weights = engine["param_bytes"] - embed * itemsize
    conv = (2 * (hf.get("linear_conv_kernel_dim", 4) - 1) * conv_channels(hf)
            * itemsize)
    state = rows * n_l * (2 * state_elements(hf) * STATE_ITEMSIZE + conv)
    attention = attn_decode(hf, cells=live_tokens * n_a, rows=rows * n_a)
    return {"flops": (2 * (engine["n_params"] - embed) * rows
                      + attention["flops"]
                      + 7 * rows * n_l * state_elements(hf)),
            "bytes": weights + state + attention["bytes"],
            "state_bytes": state}
