"""The controls the limits of a DENSE hybrid of state-space and attention
layers are set between (`models/granite_hybrid.py`: a feed-forward in every
layer, one group of B and C, the family's four multipliers), beside those of
`check_hybrid.py` and `check_linear.py` (whose loop and `Variant` this
takes): what a recurrent state per slot can get wrong and what is new with
this family, each as a program that must be refused, and the sound program
beside them. Every result is a JSON line on stdout and in
`chiprun_out/check_ssm_dense/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_ssm_dense.py --config <file> \
        --seeds 11,12,13 [--cases program,residual_one,...]

Cases:

  program            the program as it is
  interleaved_decode SOUND, and must pass as `program` does: before each
                     extend call a decode step runs over the row with `live`
                     false. The state must not move.
  live_mask_off      THE MASK CONTROL: the same step with `live` true.
  int8_weights       THE PRECISION CONTROL: every MATRIX (the vectors —
                     norms, conv taps, A_log, dt_bias, D — stay) through
                     int8 per output channel and back, in the program's
                     place.
  state_bf16         THE STATE CONTROL: the recurrent state rounded to
                     bfloat16 after every call.
  residual_one       what a sub-layer gives joins x times 1 and not times
                     `residual_multiplier`.
  attention_by_sqrt  the scores times d^-0.5 and not `attention_multiplier`.
  two_groups         B and C read as two groups of half the state's width
                     (the same channels of xBC, cut elsewhere), and the
                     gated norm a group.
  no_decay           exp(dt A) left out of the recurrence (A = 0) in the
                     chunked scan and in the decode step.
  conv_not_carried   the convolution's carried rows zeroed before each
                     extend.

A tool for the PR that adds a configuration; the driver does not call it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import check_hybrid, check_limits, check_linear  # noqa: E402

# by the name behind a run's prefix (`r3_wq`: models/granite_hybrid.runs)
MATRICES = ("embed", "ssm_in", "ssm_out", "wq", "wk", "wv", "wo", "wg", "wu",
            "wd")
CASES = ("program,interleaved_decode,live_mask_off,int8_weights,state_bf16,"
         "residual_one,attention_by_sqrt,two_groups,no_decay,"
         "conv_not_carried")


def matrices_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place
    (check_linear.matrices_to_int8's rule over this family's names)."""
    import jax

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    trip = jax.jit(lambda w: dequantize_channelwise(
        *quantize_channelwise(w), dtype=w.dtype), donate_argnums=0)
    for name in params:
        if name.split("_", 1)[-1] in MATRICES:  # `r3_wq`, `embed`
            params[name] = trip(params[name])


def configurations(cfg) -> dict:
    """case -> the configuration object a control hands the program in the
    true one's place: one term of what is new with this family wrong, the
    parameters' shapes as they are."""
    return {
        "residual_one": dataclasses.replace(cfg, residual_multiplier=1.0),
        "attention_by_sqrt": dataclasses.replace(
            cfg, attention_multiplier=cfg.head_dim_**-0.5),
        "two_groups": dataclasses.replace(
            cfg, ssm_groups=2 * cfg.ssm_groups,
            ssm_state=cfg.ssm_state // 2),
    }


def variants(family) -> dict:
    """case -> the family with its serving functions changed
    (check_linear.Variant)."""
    served = check_linear.variants(family)
    return {**{case: served[case] for case in (
        "interleaved_decode", "live_mask_off", "state_bf16",
        "conv_not_carried")},
        "no_decay": check_linear.Variant(family,
                                         patch=check_hybrid.no_decay)}


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
    from llmlb_tpu.models import family_for
    from llmlb_tpu.parallel.mesh import build_mesh

    from benchmark import correctness, launcher, reference as refs

    cfg = launcher.build_cfg(config)
    family = family_for(cfg)
    reference = refs.module_for(config, args.base)
    mesh = build_mesh(launcher.mesh_config_for(cfg, len(devices)),
                      devices=devices)
    served_as, given = variants(family), configurations(cfg)
    page = int(config["engine"].get("kv_page_size", 128))
    out_dir = os.path.join(ROOT, "chiprun_out", "check_ssm_dense")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, config["model_id"] + ".jsonl")
    with open(log_path, "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            params = launcher.make_params(family, cfg, seed, mesh)

            def on_true_weights(params_, hf, ids, **kw):
                params_.clear()  # the rounded ones go first
                params_.update(launcher.make_params(family, cfg, seed, mesh))
                return reference.forward(params_, hf, ids, **kw)

            for case in args.cases.split(","):
                t = time.monotonic()
                judge = reference
                if case == "int8_weights":
                    matrices_to_int8(params)
                    judge = check_limits.like(reference, on_true_weights)
                result = correctness.check(
                    served_as.get(case, family), given.get(case, cfg), params,
                    config, spec, seed, page, judge)
                line = {"model_id": config["model_id"], "tag": args.tag,
                        "device": devices[0].device_kind, "seed": seed,
                        "case": case, "seconds": time.monotonic() - t,
                        "result": result}
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
