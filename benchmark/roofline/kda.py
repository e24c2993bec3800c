"""Operations and bytes of a decoder whose layers mix by KIMI DELTA ATTENTION
(a gated delta rule whose decay is a number a key channel, a state a slot)
or by latent attention without rotary over a latent page pool, under one
dense feed-forward and then a mixture a chip holds a share of, with a shared
expert (`models/kimi_linear.py`), from the configuration's shapes — by the
names its own `config.json` gives them: `linear_attn_config`, `kv_lora_rank`,
`num_experts`, `num_experts_per_token`, `moe_intermediate_size` — and the
program's own counters, and the names its kernels carry in a device trace.
`num_experts` are the experts THIS CHIP holds of the
`expert_parallel.experts` the router scores. Each account is of the WORK the
equations need, whatever implements it and however it is stored: the
state's bytes are 2 x rows x H K V x 4 whatever kernel moves them, and the
shared key's 64 numbers count as 64 though their cell is 128 lanes wide, so
a padded layout shows as a LOWER share, never a higher one. Four accounts:

  step_call      one layer's rule step for `rows` sequences advanced by one
                 token: each row's state (heads x key x value, float32) read
                 once and written once, its inputs (q, k, the decay a key
                 channel, v and the write strength, float32) and its output
                 beside it; 7 operations a state element (the decay; S^T k,
                 the update and S^T q a multiply and an add each).
  latent_decode  the latent layers' decode attention over `cells` live
                 (token, layer) pairs: each cell's latent and shared key
                 read once for all heads (roofline/latent_moe.py's account
                 at the cells the program counted).
  held_experts   the grouped expert products of a set of step records
                 (roofline/band_moe.py's account, imported: it reads only
                 keys this configuration has): the THREE matrices of every
                 held expert the records' `experts_touched` counted, read
                 once, and a multiply-add per held assignment and matrix
                 element.
  decode_step    one step of the burst decode program: every weight but the
                 embedding table (a step reads one row of it a sequence),
                 the held experts counted as touched and not as held, the
                 state read and written for the rows advanced (the
                 convolution's rows with it) and the latent cells alive.
                 `state_bytes` is the state's part.
"""

from __future__ import annotations

from benchmark.roofline.band_moe import (  # noqa: F401 — the same work
    ROUTED_EXPERT_OPS,
    _itemsize,
    expert_bytes,
    expert_params,
    held_experts,
)

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
STEP_OPS = ["kda_step"]
LATENT_DECODE_OPS = ["paged_latent_decode"]
STATE_ITEMSIZE = 4  # the rule's state is float32 whatever the weights are


def is_kda(hf: dict) -> bool:
    """Whether a configuration is of this family: the readers of this
    family's metrics report nothing for any other."""
    return hf.get("model_type") == "kimi_linear"


def kda_layers(hf: dict) -> int:
    return len(hf["linear_attn_config"]["kda_layers"])


def latent_layers(hf: dict) -> int:
    return len(hf["linear_attn_config"]["full_attn_layers"])


def moe_layers(hf: dict) -> int:
    return max(0, hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0))


def state_elements(hf: dict) -> int:
    """One sequence's state in one layer: heads x key x value."""
    lin = hf["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2


def conv_channels(hf: dict) -> int:
    """Channels of [q | k | v], what the convolution runs over."""
    lin = hf["linear_attn_config"]
    return 3 * lin["num_heads"] * lin["head_dim"]


def held_slots(hf: dict) -> int:
    """Expert slots a step could touch here: mixture layers x experts held."""
    return moe_layers(hf) * hf["num_experts"]


def step_call(hf: dict, *, rows: float) -> dict:
    lin = hf["linear_attn_config"]
    width = lin["num_heads"] * lin["head_dim"]
    # q, k, the decay, v, the output: a number a head and channel; the
    # write strength a number a head
    vectors = (5 * width + lin["num_heads"]) * STATE_ITEMSIZE
    return {"flops": 7 * rows * state_elements(hf),
            "bytes": rows * (2 * state_elements(hf) * STATE_ITEMSIZE
                             + vectors)}


def latent_decode(hf: dict, *, cells: float, rows: float) -> dict:
    """`cells`: live (token, layer) pairs; `rows`: (sequence, layer) pairs."""
    heads = hf["num_attention_heads"]
    latent, rope = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    return {"flops": 2 * cells * heads * (2 * latent + rope),
            "bytes": (cells * (latent + rope)
                      + rows * heads * (2 * latent + rope)) * _itemsize(hf)}


def decode_step(hf: dict, engine: dict, *, live_tokens: float, rows: float,
                experts_touched: float) -> dict:
    """`live_tokens`: tokens of context alive, summed over the sequences;
    `rows`: sequences the step advances; `experts_touched`: distinct held
    experts a step reads, summed over the mixture layers."""
    itemsize = _itemsize(hf)
    embed = hf["vocab_size"] * hf["hidden_size"]
    n_k, n_a = kda_layers(hf), latent_layers(hf)
    weights = (engine["param_bytes"] - embed * itemsize
               - (held_slots(hf) - experts_touched) * expert_bytes(hf))
    taps = hf["linear_attn_config"].get("short_conv_kernel_size", 4)
    conv = 2 * (taps - 1) * conv_channels(hf) * itemsize
    state = rows * n_k * (2 * state_elements(hf) * STATE_ITEMSIZE + conv)
    attention = latent_decode(hf, cells=live_tokens * n_a, rows=rows * n_a)
    share = hf["num_experts"] / (hf.get("expert_parallel") or {}).get(
        "experts", hf["num_experts"])
    active = (engine["n_params"] - embed - held_slots(hf) * expert_params(hf)
              + moe_layers(hf) * hf["num_experts_per_token"] * share
              * expert_params(hf))
    return {"flops": (2 * active * rows + attention["flops"]
                      + 7 * rows * n_k * state_elements(hf)),
            "bytes": weights + state + attention["bytes"],
            "state_bytes": state}
