"""Operations and bytes of a DENSE hybrid of state-space and attention
layers, a feed-forward in every layer and the head tied to the embedding
table (`models/granite_hybrid.py`), from the configuration's shapes and the
program's own counters, and the names its kernels carry in a device trace.
Each account is of the WORK the equations need, whatever implements it and
however it is stored: a kernel that walks slots that do not decode, or a
pool stored wider than its heads, moves more bytes than are counted here
and shows as a LOWER share, never a higher one. Three accounts, and `work`
giving all three for one decode step:

  ssm_step_call     one layer's state step for `rows` sequences advanced by
                    one token: each row's recurrent state (heads x channels
                    x state, float32) read once and written once, its inputs
                    (x, dt, B, C) and its output beside it; 6 operations a
                    state element (decay, outer product and sum; the
                    readout's multiply and add; dt x).
  attn_decode_call  the attention layers' decode attention over `cells` live
                    (token, layer) pairs: each cell's K and V of every KV
                    head read once, by the equations' heads (8 of 64: 2,048
                    B a cell) whatever the pool stores; a multiply-add per
                    query head, cell and channel, twice.
  decode_step       one step of the burst decode program: EVERY weight, the
                    embedding table among them once (it is the head: a step
                    reads it whole, and the rows it reads as an embedding
                    are nothing beside that), plus the state read and
                    written for the rows advanced (the convolution's rows
                    with it), plus the keys and values alive in the
                    attention layers. `state_bytes` is the state's part.
"""

from __future__ import annotations

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
SSM_STEP_OPS = ["ssm_decode_step"]
ATTN_DECODE_OPS = ["paged_flash_decode"]
STATE_ITEMSIZE = 4  # the recurrent state is float32 whatever the weights are
MAMBA, ATTENTION = "mamba", "attention"


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def layers(hf: dict, kind: str) -> int:
    """Layers of a kind (`mamba`, `attention`)."""
    return hf["layer_types"].count(kind)


def state_elements(hf: dict) -> int:
    """One sequence's recurrent state in one layer."""
    return hf["mamba_n_heads"] * hf["mamba_d_head"] * hf["mamba_d_state"]


def conv_channels(hf: dict) -> int:
    """Channels of [x | B | C], what the convolution runs over."""
    return (hf["mamba_n_heads"] * hf["mamba_d_head"]
            + 2 * hf["mamba_n_groups"] * hf["mamba_d_state"])


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def ssm_step_call(hf: dict, *, rows: float) -> dict:
    """`rows`: (sequence, layer) pairs advanced."""
    inner = hf["mamba_n_heads"] * hf["mamba_d_head"]
    vectors = (2 * inner + hf["mamba_n_heads"]
               + 2 * hf["mamba_n_groups"] * hf["mamba_d_state"]
               ) * _itemsize(hf)
    return {"flops": 6 * rows * state_elements(hf),
            "bytes": rows * (2 * state_elements(hf) * STATE_ITEMSIZE
                             + vectors)}


def attn_decode_call(hf: dict, *, cells: float, rows: float) -> dict:
    """`cells`: live (token, layer) pairs; `rows`: (sequence, layer) pairs."""
    heads = hf["num_attention_heads"]
    d, itemsize = head_dim(hf), _itemsize(hf)
    return {"flops": 4 * cells * heads * d,
            "bytes": (cells * 2 * hf["num_key_value_heads"] * d
                      + rows * 2 * heads * d) * itemsize}


def decode_step(hf: dict, engine: dict, *, live_tokens: float,
                rows: float) -> dict:
    """`live_tokens`: tokens of context alive, summed over the sequences;
    `rows`: sequences the step advances."""
    n_m, n_a = layers(hf, MAMBA), layers(hf, ATTENTION)
    conv = (2 * (hf["mamba_d_conv"] - 1) * conv_channels(hf)
            * _itemsize(hf))
    state = rows * n_m * (2 * state_elements(hf) * STATE_ITEMSIZE + conv)
    attention = attn_decode_call(hf, cells=live_tokens * n_a, rows=rows * n_a)
    return {"flops": (2 * engine["n_params"] * rows + attention["flops"]
                      + 6 * rows * n_m * state_elements(hf)),
            "bytes": engine["param_bytes"] + state + attention["bytes"],
            "state_bytes": state}


def work(hf: dict, engine: dict, *, live_tokens: float, rows: float) -> dict:
    """The three accounts of ONE decode step at `rows` sequences advanced and
    `live_tokens` of context alive: a call of each kernel (one layer's) and
    the whole step."""
    return {
        "ssm_step_call": ssm_step_call(hf, rows=rows),
        "attn_decode_call": attn_decode_call(hf, cells=live_tokens,
                                             rows=rows),
        "decode_step": decode_step(hf, engine, live_tokens=live_tokens,
                                   rows=rows),
    }
