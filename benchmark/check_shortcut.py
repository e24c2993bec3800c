"""The controls the limits of a configuration of DOUBLE layers with a
shortcut-connected mixture and zero-compute experts are set between
(`models/longcat_flash.py`), beside those of `check_config.py` and
`check_limits.py` (whose loop this repeats; their leaf names and routing
rule are the other mixtures'): what the new layer can get wrong, each as a
program that must be refused, and the sound program beside them. Every
result is a JSON line on stdout and in
`chiprun_out/check_shortcut/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_shortcut.py --config <file> \
        --seeds 11,12,13 [--cases program,int8_weights,...]

Cases:

  program          the program as it is
  int8_weights     THE PRECISION CONTROL, as `check_limits.py` has it, over
                   the MATRICES by name (the norms and the choice bias
                   stay): each through int8 per output channel and back.
  unbiased_choice  the choice made without the bias, score + bias reported:
                   sound logits, sound scores, a wrong choice.
  zeroed_chosen_expert  in EACH layer, `we_down` of the HELD expert the
                   compared positions chose most there, zeroed on the
                   program's side (read from `program`, which has to run
                   before it). One a layer and not one in all: at 16 held of
                   512, a dozen positions choose a held expert once to four
                   times a layer, with an unnormalised weight near 0.06.
  zero_dropped     the zero-compute experts dropped: an assignment of one
                   adds nothing, where it should add weight x the token.
  shortcut_early   the mixture's output added where it is computed, one
                   sub-layer early: the second attention and the second
                   dense feed-forward see it.
  scales_off       the two LoRA scales left out (`mla_scale_q_lora`,
                   `mla_scale_kv_lora` as if false).
  unfollowed       as `check_config.py` has it.

A tool for the PR that adds a configuration; the driver does not call it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import check_config, check_hybrid, check_limits  # noqa: E402

MATRICES = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo", "wg", "wu", "wd",
            "router", "we_gate", "we_up", "we_down")
CASES = ("program,int8_weights,unbiased_choice,zeroed_chosen_expert,"
         "zero_dropped,shortcut_early,scales_off,unfollowed")


def matrices_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place
    (check_limits.rounded_to_int8's rule, by name under either sub-layer's
    prefix)."""
    import jax

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    trip = jax.jit(lambda w: dequantize_channelwise(
        *quantize_channelwise(w), dtype=w.dtype), donate_argnums=0)
    for name in params:
        if name in ("embed", "lm_head") or name[3:] in MATRICES:
            params[name] = trip(params[name])


@contextlib.contextmanager
def routing_rule(change):
    """While a program is traced: `ops.moe.softmax_bias_routing` gives
    `change(real rule, logits, bias, k, **rule)`."""
    from llmlb_tpu.ops import moe

    real = moe.softmax_bias_routing
    moe.softmax_bias_routing = lambda *a, **kw: change(real, *a, **kw)
    try:
        yield
    finally:
        moe.softmax_bias_routing = real


def by_score_alone(real, logits, bias, k, **rule):
    weights, chosen, scores = real(logits, bias * 0, k, **rule)
    return weights, chosen, scores + bias


def zero_experts_dropped(experts: int):
    import jax.numpy as jnp

    def change(real, logits, bias, k, **rule):
        weights, chosen, scores = real(logits, bias, k, **rule)
        return jnp.where(chosen >= experts, 0.0, weights), chosen, scores

    return change


@contextlib.contextmanager
def shortcut_early():
    """While a program is traced: each layer's mixture is added by the
    sub-layer that computes it, and nothing is deferred."""
    from llmlb_tpu.models import longcat_flash

    real = longcat_flash._groups

    def groups(cfg, live=None):
        out = []
        for g in real(cfg, live):
            if g.branch is not None:
                def both(lp, h, valid, lora_idx, g=g):
                    s, routing = g.branch(lp, h, valid, lora_idx)
                    return g.mlp_fn(lp, h, valid, lora_idx) + s, routing

                g = g._replace(mlp_fn=both, branch=None)
            out.append(g._replace(joins=False))
        return out

    longcat_flash._groups = groups
    try:
        yield
    finally:
        longcat_flash._groups = real


class OtherConfig:
    """`family` whose three paged serving functions are handed `change(cfg)`
    in the configuration's place."""

    def __init__(self, family, change):
        self._family = family
        for name in check_limits.SERVING:
            setattr(self, name, self._with(getattr(family, name), change))

    def __getattr__(self, name):
        return getattr(self._family, name)

    @staticmethod
    def _with(fn, change):
        def served(params, cfg, *args, routing: bool = False, **kw):
            return fn(params, change(cfg), *args, routing=routing, **kw)

        return served


def variants(family, cfg) -> dict:
    def patched(patch):
        return check_hybrid.Variant(family, patch=patch)

    return {
        "unbiased_choice": patched(lambda: routing_rule(by_score_alone)),
        "zero_dropped": patched(lambda: routing_rule(
            zero_experts_dropped(cfg.router_experts))),
        "shortcut_early": patched(shortcut_early),
        "scales_off": OtherConfig(family, lambda c: dataclasses.replace(
            c, q_lora_scale=1.0, kv_lora_scale=1.0)),
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
    served_as = variants(family, cfg)
    first, held = cfg.held_experts
    page = int(config["engine"].get("kv_page_size", 128))
    out_dir = os.path.join(ROOT, "chiprun_out", "check_shortcut")
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
                        at = heard[0][:, check_limits.compared_positions(
                            spec)] - first  # [L, positions, k]
                        experts = np.asarray([np.bincount(
                            a[(a >= 0) & (a < held)], minlength=held).argmax()
                            for a in at])
                        note = {"zeroed": experts.tolist(), "read_by": int(
                            (at == experts[:, None, None]).any(-1).sum())}
                        judge = check_limits.broken_leaf(
                            stack, params, reference, "s0_we_down",
                            (np.arange(len(experts)), experts), None)
                    result = correctness.check(served, cfg, params, config,
                                               spec, seed, page, judge)
                if case == "program" and heard:
                    chosen = heard[0]
                    note = {"chosen_zero_share": float(
                        (chosen >= cfg.router_experts).mean()),
                        "chosen_held_share": float(
                        ((chosen >= first) & (chosen < first + held)).mean())}
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
