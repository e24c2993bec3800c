"""The controls the limits of a configuration whose layers mix their tokens
by a GATED SHORT CONVOLUTION (two carried rows a slot beside the page pool)
or by QK-normed rotary attention over a pool of packed rows, under dense
feed-forwards and then a sigmoid-routed mixture held whole
(`models/lfm2_moe.py`), are set between, beside those of `check_config.py`,
`check_limits.py`, `check_hybrid.py` and `check_band.py` (whose loop and
patches this takes): what is new with this family, each as a program that
must be refused, and the sound program beside them. Every result is a JSON
line on stdout and in `chiprun_out/check_conv_moe/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_conv_moe.py --config <file> \
        --seeds 11,12,13 [--cases program,no_b_gate,...]

Cases:

  program            the program as it is
  interleaved_decode SOUND, and must pass as `program` does: before each
                     extend call a decode step runs over the row with `live`
                     false, as the engine's burst steps a slot that is
                     mid-way through a chunked prefill. The carried rows
                     must not move.
  live_mask_off      THE MASK CONTROL: the same step with `live` true.
  int8_weights       THE PRECISION CONTROL, as `check_limits.py` has it, over
                     the MATRICES by name (the norms, the convolution's taps
                     and the choice bias stay): each through int8 per output
                     channel and back.
  no_b_gate          the gate in front of the convolution dropped: z = u.
  silu_behind_conv   an activation behind the convolution, as a Mamba
                     layer's has.
  conv_not_carried   the carried rows zeroed before each extend: a chunk
                     that convolves as if it began a sequence.
  no_qk_norm         the norm over each head of q and of k left out.
  no_rotary          the rotary embedding left out.
  unfollowed, unbiased_choice, zeroed_chosen_expert
                     as `check_config.py` and `check_limits.py` have them; the
                     zeroed expert is the one the compared positions chose
                     most in the first mixture layer.

A tool for the PR that adds a configuration; the driver does not call it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
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
)

MATRICES = ("conv_in", "conv_out", "wq", "wk", "wv", "wo", "wg", "wu", "wd",
            "router", "we_gate", "we_up", "we_down")
PREFIXES = ("c_", "a_", "dense_")  # of the stacks; the mixtures' have none
CASES = ("program,interleaved_decode,live_mask_off,int8_weights,no_b_gate,"
         "silu_behind_conv,conv_not_carried,no_qk_norm,no_rotary,unfollowed,"
         "unbiased_choice,zeroed_chosen_expert")


def matrices_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place
    (check_limits.rounded_to_int8's rule, by name under a stack's prefix)."""
    import jax

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    trip = jax.jit(lambda w: dequantize_channelwise(
        *quantize_channelwise(w), dtype=w.dtype), donate_argnums=0)
    for name in list(params):
        base = next((name[len(p):] for p in PREFIXES if name.startswith(p)),
                    name)
        if name == "embed" or base in MATRICES:
            params[name] = trip(params[name])


def _gate_in_open(real):
    import jax.numpy as jnp

    def bcu(cfg, lp, x):
        _, gate_out, u = real(cfg, lp, x)
        return jnp.ones_like(u), gate_out, u

    return bcu


def _silu_behind(real):
    import jax

    return lambda *a, act=None, **kw: real(*a, act=jax.nn.silu, **kw)


def variants(family) -> dict:
    """case -> the family with its serving functions changed
    (check_hybrid.Variant)."""
    from llmlb_tpu.models import lfm2_moe
    from llmlb_tpu.ops import ssm

    def patched(*a):
        return check_hybrid.Variant(family, patch=check_band.replaced(*a))

    def rows_forgotten(ck, cv):
        return ck._replace(state=ck.state * 0), cv

    return {
        "interleaved_decode": check_hybrid.Variant(family, step_live=False),
        "live_mask_off": check_hybrid.Variant(family, step_live=True),
        "no_b_gate": patched(lfm2_moe, "_bcu", _gate_in_open),
        "silu_behind_conv": patched(ssm, "causal_conv", _silu_behind),
        "conv_not_carried": check_hybrid.Variant(
            family, before_extend=rows_forgotten),
        "no_qk_norm": patched(lfm2_moe, "rms_norm",
                              check_band._heads_unnormed),
        "no_rotary": patched(lfm2_moe, "apply_rope",
                             lambda _real: lambda x, positions, inv_freq: x),
        "unbiased_choice": check_limits.UnbiasedChoice(family),
    }


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
    page = int(config["engine"].get("kv_page_size", 128))
    out_dir = os.path.join(ROOT, "chiprun_out", "check_conv_moe")
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
                        expert = int(np.bincount(at.ravel()).argmax())
                        note = {"zeroed": [0, expert], "read_by": int(
                            (at == expert).any(-1).sum())}
                        judge = check_limits.broken_leaf(
                            stack, params, reference, "we_down", (0, expert),
                            None)
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
