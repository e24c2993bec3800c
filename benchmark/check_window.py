"""The controls the limits of a configuration with WINDOW attention layers
beside global ones are set between (`models/mimo_v2.py`), beside those of
`check_config.py`, `check_limits.py` and `check_hybrid.py` (whose loop this
repeats): what a ring a slot, a sink, keys wider than values, a partial
rotary embedding and two kinds of attention can get wrong, each as a program
that must be refused, and the sound program beside them. Every result is a
JSON line on stdout and in `chiprun_out/check_window/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_window.py --config <file> \
        --seeds 11,12,13 [--cases program,no_window,...]

Cases:

  program          the program as it is
  interleaved_decode  SOUND, and must pass as `program` does: before each
                   extend call a decode step runs over the row with `live`
                   false, as the engine's burst steps a slot that is mid-way
                   through a chunked prefill. The ring must not move.
  int8_weights     THE PRECISION CONTROL, as `check_limits.py` has it, over
                   the MATRICES by name (the norms, the sinks and the choice
                   bias stay): each through int8 per output channel and back.
  no_window        the window left out of the window layers: a ring as long
                   as the whole sequence, every position seen.
  window_129       the window off by one: a position sees the 128 before it.
  no_sink          the sink left out of the softmax's denominator.
  no_value_scale   `attention_value_scale` left out.
  full_rotary      rotary over all 192 numbers of a head, not the first 64.
  one_rope_base    the window layers rotated at the global layers' base.
  window_4_kv_heads  the window layers read at 4 KV heads: query head r on
                   KV head r // 16 of the first four, not r // 8 of eight.
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
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import (  # noqa: E402
    check_config,
    check_hybrid,
    check_limits,
    check_shortcut,
)

MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "router", "we_gate",
            "we_up", "we_down")
PREFIXES = ("g_", "w_", "dense_")  # of the stacks; the mixtures' have none
CASES = ("program,interleaved_decode,int8_weights,no_window,window_129,"
         "no_sink,no_value_scale,full_rotary,one_rope_base,"
         "window_4_kv_heads,unfollowed,unbiased_choice,zeroed_chosen_expert")


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
        if name in ("embed", "lm_head") or base in MATRICES:
            params[name] = trip(params[name])


class OtherConfig(check_shortcut.OtherConfig):
    """`family` whose POOL, too, is made for `change(cfg)`: a ring is as
    long as the changed configuration's window."""

    def __init__(self, family, change):
        super().__init__(family, change)
        self.init_kv_pages = lambda cfg, *a, **kw: family.init_kv_pages(
            change(cfg), *a, **kw)


@contextlib.contextmanager
def window_on_four_kv_heads():
    """While a program is traced: a window layer's query head r reads KV
    head r // 16 of the first four, as a global layer's does."""
    import jax.numpy as jnp

    from llmlb_tpu.models import mimo_v2

    real = mimo_v2._qkv

    def qkv(cfg, lp, x, positions, kind):
        q, k, v = real(cfg, lp, x, positions, kind)
        if kind == mimo_v2.WINDOW:
            few, twice = cfg.num_kv_heads, cfg.window_kv_heads // cfg.num_kv_heads
            k, v = (jnp.repeat(a[:, :, :few], twice, axis=2) for a in (k, v))
        return q, k, v

    mimo_v2._qkv = qkv
    try:
        yield
    finally:
        mimo_v2._qkv = real


def variants(family, total: int) -> dict:
    def other(**change):
        return OtherConfig(family,
                           lambda c: dataclasses.replace(c, **change))

    ring = 256
    while ring < total + 1:
        ring *= 2
    return {
        "interleaved_decode": check_hybrid.Variant(family, step_live=False),
        "no_window": other(sliding_window=ring),
        "window_129": OtherConfig(family, lambda c: dataclasses.replace(
            c, sliding_window=c.sliding_window + 1)),
        "no_sink": other(window_sink=False),
        "no_value_scale": other(value_scale=1.0),
        "full_rotary": other(partial_rotary_factor=1.0),
        "one_rope_base": OtherConfig(family, lambda c: dataclasses.replace(
            c, window_rope_theta=c.rope_theta)),
        "window_4_kv_heads": check_hybrid.Variant(
            family, patch=window_on_four_kv_heads),
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
    served_as = variants(family, check_limits.compared_positions(spec)[-1] + 1)
    first, held = cfg.held_experts
    page = int(config["engine"].get("kv_page_size", 128))
    out_dir = os.path.join(ROOT, "chiprun_out", "check_window")
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
                        expert = int(np.bincount(mine.ravel(),
                                                 minlength=1).argmax())
                        note = {"zeroed": [0, expert], "read_by": int(
                            (at == first + expert).any(-1).sum())}
                        judge = check_limits.broken_leaf(
                            stack, params, reference, "we_down", (0, expert),
                            None)
                    result = correctness.check(served, cfg, params, config,
                                               spec, seed, page, judge)
                if case == "program" and heard:
                    chosen = heard[0]
                    note = {"chosen_held_share": float(
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
