"""Operations and bytes of a hybrid of state-space, attention and expert
layers (`models/nemotron_h.py`), from the configuration's shapes and the
program's own counters, and the names its kernels carry in a device trace.
Each account is of the WORK, whatever implements it. Three accounts:

  ssm_step_call   one layer's state step for `rows` sequences advanced by one
                  token: each row's recurrent state (heads x head_dim x
                  state, float32) read once and written once, its inputs (x,
                  dt, B, C) and its output beside it; 6 operations a state
                  element (decay, outer product and sum; the readout's
                  multiply and add; dt x).
  held_experts    the grouped expert products of a set of step records: the
                  TWO matrices of every expert the records' `experts_touched`
                  counted, read once, and a multiply-add per assignment and
                  matrix element. Only the experts this chip holds are
                  counted, as only they are computed; the shared expert is a
                  plain matmul outside these kernels and outside this account.
  decode_step     one step of the burst decode program: every weight but the
                  embedding table (a step reads one row of it a sequence), the
                  routed experts counted as touched and not as held, plus the
                  state read and written for the rows advanced (the
                  convolution's rows with it), plus the keys and values alive
                  in the attention layers. `state_bytes` is the state's part.
"""

from __future__ import annotations

# The device operations of each kernel, as `trace.op_label` prints them
# (benchmark/samples.matching takes the shape suffix and instance numbers).
SSM_STEP_OPS = ["ssm_decode_step"]
# ops/pallas_moe.py's grouped matmul, and what `jax.lax.ragged_dot` lowers to
# on a TPU where the program falls back to it.
ROUTED_EXPERT_OPS = ["grouped_expert_matmul", "ragged-dot-none",
                     "ragged-dot-metadata"]
STATE_ITEMSIZE = 4  # the recurrent state is float32 whatever the weights are


def _itemsize(hf: dict) -> int:
    return 2 if hf.get("torch_dtype", "bfloat16") == "bfloat16" else 4


def layers(hf: dict, kind: str) -> int:
    """Layers of a kind (`M`, `*`, `E`) in the configuration's pattern."""
    return hf["hybrid_override_pattern"].count(kind)


def state_elements(hf: dict) -> int:
    """One sequence's recurrent state in one layer."""
    return hf["mamba_num_heads"] * hf["mamba_head_dim"] * hf["ssm_state_size"]


def expert_bytes(hf: dict) -> int:
    """One routed expert's two matrices."""
    return 2 * hf["hidden_size"] * hf["moe_intermediate_size"] * _itemsize(hf)


def ssm_step_call(hf: dict, *, rows: float) -> dict:
    inner = hf["mamba_num_heads"] * hf["mamba_head_dim"]
    vectors = (2 * inner + hf["mamba_num_heads"]
               + 2 * hf["n_groups"] * hf["ssm_state_size"]) * _itemsize(hf)
    return {"flops": 6 * rows * state_elements(hf),
            "bytes": rows * (2 * state_elements(hf) * STATE_ITEMSIZE
                             + vectors)}


def held_experts(hf: dict, *, experts_touched: float,
                 assignments: float) -> dict:
    per_assignment = 2 * 2 * hf["hidden_size"] * hf["moe_intermediate_size"]
    rows = assignments * 2 * (hf["hidden_size"]
                              + hf["moe_intermediate_size"]) * _itemsize(hf)
    return {"flops": assignments * per_assignment,
            "bytes": experts_touched * expert_bytes(hf) + rows}


def decode_step(hf: dict, engine: dict, *, live_tokens: float, rows: float,
                experts_touched: float) -> dict:
    """`experts_touched`: distinct held experts a step reads, summed over
    the expert layers. `rows`: sequences the step advances."""
    itemsize = _itemsize(hf)
    hidden = hf["hidden_size"]
    embed = hf["vocab_size"] * hidden
    n_m, n_a, n_e = (layers(hf, k) for k in "M*E")
    held = n_e * hf["n_routed_experts"]
    weights = (engine["param_bytes"] - embed * itemsize
               - (held - experts_touched) * expert_bytes(hf))
    conv = 2 * (hf.get("conv_kernel", 4) - 1) * (
        hf["mamba_num_heads"] * hf["mamba_head_dim"]
        + 2 * hf["n_groups"] * hf["ssm_state_size"]) * itemsize
    state = rows * n_m * (2 * state_elements(hf) * STATE_ITEMSIZE + conv)
    head_dim = hf.get("head_dim") or hf["attention_head_dim"]
    cache = (live_tokens * n_a * 2 * hf["num_key_value_heads"] * head_dim
             * itemsize)
    per_expert = 2 * hidden * hf["moe_intermediate_size"]
    share = hf["n_routed_experts"] / (hf.get("expert_parallel") or {}).get(
        "experts", hf["n_routed_experts"])
    active = (engine["n_params"] - embed - held * per_expert
              + n_e * hf["num_experts_per_tok"] * share * per_expert)
    attention = 4 * live_tokens * hf["num_attention_heads"] * head_dim * n_a
    return {"flops": (2 * active * rows + attention
                      + 6 * rows * n_m * state_elements(hf)),
            "bytes": weights + state + cache, "state_bytes": state}
