"""The controls the limits of a configuration whose layers mix their tokens
by KIMI DELTA ATTENTION (a delta rule whose decay is a number a KEY CHANNEL,
its state a slot beside a LATENT page pool) or by latent attention without
rotary, under a mixture a chip holds a share of (`models/kimi_linear.py`),
are set between, beside those of `check_config.py`, `check_limits.py`,
`check_hybrid.py` and `check_linear.py` (whose loop and patches this takes):
what is new with this family, each as a program that must be refused, and
the sound program beside them. Every result is a JSON line on stdout and in
`chiprun_out/check_kda/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_kda.py --config <file> \
        --seeds 11,12,13 [--cases program,decay_channel_mean,...]

Cases:

  program            the program as it is
  interleaved_decode SOUND, and must pass as `program` does: before each
                     extend call a decode step runs over the row with `live`
                     false, as the engine's burst steps a slot that is
                     mid-way through a chunked prefill. The state must not
                     move.
  live_mask_off      THE MASK CONTROL: the same step with `live` true.
  int8_weights       THE PRECISION CONTROL, as `check_limits.py` has it, over
                     the MATRICES by name under a run's prefix (the norms,
                     the convolution's taps, A_log, dt_bias and the choice
                     bias stay): each through int8 per output channel and
                     back.
  state_bf16         THE STATE CONTROL: the rule's state rounded to bfloat16
                     after every call, what a bf16 state pool keeps.
  decay_channel_mean THE CONTROL THAT TELLS KDA FROM A DECAY A HEAD: the
                     decay of a head's key channels collapsed to their mean,
                     in the chunked form and in the step. If it passes, the
                     cell is not measuring this model.
  beta_doubled       b = 2 sigmoid(.) where this family says sigmoid(.).
  keys_rotated       the latent block rotates the shared key and the
                     queries' last numbers, as deepseek_v3's always does.
  conv_not_carried   the convolution's carried rows zeroed before each
                     extend: a chunk that convolves as if it began a sequence.
  unfollowed, unbiased_choice, zeroed_chosen_expert
                     as `check_config.py` and `check_limits.py` have them; the
                     zeroed expert is the HELD expert the compared positions
                     chose most in the first mixture layer.

A tool for the PR that adds a configuration; the driver does not call it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import (  # noqa: E402
    check_band,
    check_config,
    check_hybrid,
    check_limits,
    check_linear,
)

MATRICES = ("wqkv", "w_low", "wf_b", "wg_b", "wo_kda", "wq", "wkv_a", "wk_b",
            "wv_b", "wo", "wg", "wu", "wd", "router", "we_gate", "we_up",
            "we_down", "ws_gu", "ws_down")
RUN = re.compile(r"^r\d+_")  # a run's prefix (models/kimi_linear.runs)
CASES = ("program,interleaved_decode,live_mask_off,int8_weights,state_bf16,"
         "decay_channel_mean,beta_doubled,keys_rotated,conv_not_carried,"
         "unfollowed,unbiased_choice,zeroed_chosen_expert")


def matrices_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place
    (check_limits.rounded_to_int8's rule, by name under a run's prefix)."""
    import jax

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    trip = jax.jit(lambda w: dequantize_channelwise(
        *quantize_channelwise(w), dtype=w.dtype), donate_argnums=0)
    for name in list(params):
        if name in ("embed", "lm_head") or RUN.sub("", name) in MATRICES:
            params[name] = trip(params[name])


@contextlib.contextmanager
def decay_collapsed():
    """While a program is traced: a head's decay the MEAN of its key
    channels', in both forms of the rule (the rank stays, so the kernel and
    the chunked form are the program's own)."""
    import jax.numpy as jnp

    from llmlb_tpu.ops import delta_rule

    real = delta_rule.delta_rule_chunked, delta_rule.delta_rule_step

    def mean(a):
        return jnp.broadcast_to(jnp.mean(a, axis=-1, keepdims=True), a.shape)

    def chunked(q, k, v, g, *rest, **kw):
        return real[0](q, k, v, jnp.log(mean(jnp.exp(g))), *rest, **kw)

    def step(pool, layer, q, k, v, alpha, *rest, **kw):
        return real[1](pool, layer, q, k, v, mean(alpha), *rest, **kw)

    delta_rule.delta_rule_chunked, delta_rule.delta_rule_step = chunked, step
    try:
        yield
    finally:
        delta_rule.delta_rule_chunked, delta_rule.delta_rule_step = real


def _rotating(real):
    """models/kimi_linear._attention whose block rotates as deepseek_v3's."""
    from llmlb_tpu.models import deepseek_v3

    def attention(cfg):
        def block(cfg_, *a, **kw):
            return deepseek_v3._mla_block(
                dataclasses.replace(cfg_, mla_nope=False), *a, **kw)

        return real(cfg)._replace(block=block)

    return attention


def variants(family) -> dict:
    """case -> the family with its serving functions changed
    (check_hybrid.Variant)."""
    import jax.numpy as jnp

    from llmlb_tpu.models import kimi_linear

    def state_to_bf16(ck, cv):
        return ck._replace(state=ck.state.astype(jnp.bfloat16)
                           .astype(ck.state.dtype)), cv

    def rows_forgotten(ck, cv):
        return ck, cv._replace(state=cv.state * 0)

    return {
        "interleaved_decode": check_hybrid.Variant(family, step_live=False),
        "live_mask_off": check_hybrid.Variant(family, step_live=True),
        "state_bf16": check_hybrid.Variant(family, after=state_to_bf16),
        "decay_channel_mean": check_hybrid.Variant(family,
                                                   patch=decay_collapsed),
        "beta_doubled": check_hybrid.Variant(family, patch=functools.partial(
            check_linear.rule_changed, beta_scale=2.0)),
        "keys_rotated": check_hybrid.Variant(family, patch=check_band.replaced(
            kimi_linear, "_attention", _rotating)),
        "conv_not_carried": check_hybrid.Variant(
            family, before_extend=rows_forgotten),
        "unbiased_choice": check_limits.UnbiasedChoice(family),
    }


def first_mixture_down(params: dict) -> str:
    """The leaf that holds the first mixture layer's down projections: the
    first run with experts (its row 0)."""
    return min((n for n in params if RUN.sub("", n) == "we_down"),
               key=lambda n: int(n[1:n.index("_")]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--base", default=ROOT,
                    help="the directory of the manifest, for its references")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--cases", default=CASES)
    ap.add_argument("--sizes", default="{}",
                    help="JSON laid over the file's correctness block")
    ap.add_argument("--tag", default="", help="goes into every line")
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    spec = {**config["correctness"], **json.loads(args.sizes)}

    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    configure_compile_cache()
    devices = resolve_backend()
    import numpy as np

    from llmlb_tpu.models import family_for
    from llmlb_tpu.parallel.mesh import build_mesh

    from benchmark import correctness, launcher, reference as refs

    cfg = launcher.build_cfg(config)
    family = family_for(cfg)
    reference = refs.module_for(config, args.base)
    mesh = build_mesh(launcher.mesh_config_for(cfg, len(devices)),
                      devices=devices)
    served_as = variants(family)
    first, held = cfg.held_experts
    page = int(config["engine"].get("kv_page_size", 128))
    out_dir = os.path.join(ROOT, "chiprun_out", "check_kda")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, config["model_id"] + ".jsonl")
    with open(log_path, "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            params = launcher.make_params(family, cfg, seed, mesh)
            heard = []  # the program's choices [L, T, k], once told

            def hearing(params_, hf, ids, **kw):
                heard.append(np.asarray(kw["follow"]))
                return reference.forward(params_, hf, ids, **kw)

            def on_true_weights(params_, hf, ids, **kw):
                params_.clear()  # the rounded ones go first
                params_.update(launcher.make_params(family, cfg, seed, mesh))
                return reference.forward(params_, hf, ids, **kw)

            for case in args.cases.split(","):
                t = time.monotonic()
                note = {}
                with contextlib.ExitStack() as stack:
                    served = served_as.get(case, family)
                    judge = check_config.reference_for(case, reference, None)
                    if case == "program":
                        judge = check_limits.like(reference, hearing)
                    elif case == "int8_weights":
                        matrices_to_int8(params)
                        judge = check_limits.like(reference, on_true_weights)
                    elif case == "zeroed_chosen_expert":
                        if not heard:
                            raise SystemExit(f"{case}: run `program` first")
                        at = heard[0][0, check_limits.compared_positions(spec)]
                        mine = at[(at >= first) & (at < first + held)] - first
                        expert = int(np.bincount(mine.ravel()).argmax())
                        leaf = first_mixture_down(params)
                        note = {"zeroed": [leaf, 0, expert], "read_by": int(
                            (at == first + expert).any(-1).sum())}
                        judge = check_limits.broken_leaf(
                            stack, params, reference, leaf, (0, expert), None)
                    result = correctness.check(served, cfg, params, config,
                                               spec, seed, page, judge)
                line = {"model_id": config["model_id"], "tag": args.tag,
                        "device": devices[0].device_kind, "seed": seed,
                        "case": case, "seconds": time.monotonic() - t,
                        **note, "result": result}
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
