"""The controls a configuration's limits are set between, beside those of
`check_config.py` (whose loop and cases this repeats, and adds to): each of
`tolerance`, `router_tolerance` and `flip_margin_multiple` lies between the
largest reading of the sound program and the smallest of a control that
must fail it. Every result is a JSON line on stdout and in
`chiprun_out/check_limits/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_limits.py --config <file> \
        --seeds 11,12,13 --cases program,int8_weights,unbiased_choice

Cases, beside `check_config.py`'s (`program`, `unfollowed`, `zeroed_expert`,
`permuted_router`):

  int8_weights     THE PRECISION CONTROL. The program with every matrix
                   rounded to int8 per output channel and back, the nearest
                   precision below the bf16 a configuration states, against
                   the reference on the true weights. Rounded in place
                   (donated); for the reference's pass the true weights are
                   made again from the seed, so nothing is held twice.
  unbiased_choice  THE WRONG CHOICE. The program chooses its experts by score
                   alone and reports score + bias, as a sound one does: its
                   logits are sound (the reference follows the choice), its
                   scores are sound, and the choice is wrong by far more
                   than rounding. What `flip_margin_multiple` is for.
  zeroed_chosen_expert  `zeroed_expert` on the expert that the compared
                   positions chose most often in the first expert layer
                   (read from the `program` case of the same seed, which has
                   to run before it): at 128 experts the fixed (0, 1) is read
                   by none of a dozen positions in a seed out of three.

A tool for the PR that adds a configuration; the driver does not call it.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import inspect
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import check_config  # noqa: E402

SERVING = ("prefill_into_pages", "prefill_extend_pages", "decode_step_paged")
NOT_ROUNDED = ("ln_", "router_bias")  # norms and the choice bias: vectors


def rounded_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place: symmetric,
    one scale per output channel over the contraction axis (the last but
    one), llmlb_tpu/quant's rule for served int8 weights."""
    import jax

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    trip = jax.jit(lambda w: dequantize_channelwise(
        *quantize_channelwise(w), dtype=w.dtype), donate_argnums=0)
    for name in list(params):
        bare = name.split("dense_")[-1]
        if params[name].ndim >= 2 and not bare.startswith(NOT_ROUNDED):
            params[name] = trip(params[name])


class UnbiasedChoice:
    """`family` with its three paged serving functions traced apart (another
    function than the program jits, so another trace cache) while
    `ops.moe.sigmoid_bias_routing` chooses and weighs without the bias and
    still reports score + bias."""

    def __init__(self, family):
        self._family = family
        for name in SERVING:
            setattr(self, name, self._apart(getattr(family, name)))

    def __getattr__(self, name):
        return getattr(self._family, name)

    @staticmethod
    def _apart(fn):
        import jax

        from llmlb_tpu.ops import moe

        body = fn.__wrapped__  # under the program's jax.jit
        names = inspect.signature(body).parameters

        @functools.wraps(body)
        def traced_apart(*args, **kw):
            real = moe.sigmoid_bias_routing

            def by_score_alone(logits, bias, k, **rule):
                weights, chosen, scores = real(logits, bias * 0, k, **rule)
                return weights, chosen, scores + bias

            moe.sigmoid_bias_routing = by_score_alone  # while it is traced
            try:
                return body(*args, **kw)
            finally:
                moe.sigmoid_bias_routing = real

        return jax.jit(
            traced_apart,
            static_argnames=[n for n in ("cfg", "mesh", "window", "routing")
                             if n in names],
            donate_argnames=("cache_k", "cache_v"))


def compared_positions(spec: dict) -> list[int]:
    """The positions whose logits `correctness.check` compares."""
    p0, ct = int(spec["prefill_tokens"]), int(spec.get("extend_tokens", 32))
    chunks = int(spec.get("extend_chunks", 0))
    steps = int(spec["decode_steps"])
    ends = [p0 + i * ct - 1 for i in range(chunks + 1)]
    return ends + [ends[-1] + 1 + i for i in range(steps)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--base", default=ROOT,
                    help="the directory of the manifest, for its references")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--cases", default="program,int8_weights,"
                    "unbiased_choice,zeroed_chosen_expert")
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
    unbiased = UnbiasedChoice(family)
    page = int(config["engine"].get("kv_page_size", 128))
    out_dir = os.path.join(ROOT, "chiprun_out", "check_limits")
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
                    served, judge = family, check_config.reference_for(
                        case, reference, None)  # knows `unfollowed`
                    if case == "program":
                        judge = like(reference, hearing)
                    elif case == "int8_weights":
                        rounded_to_int8(params)
                        judge = like(reference, on_true_weights)
                    elif case == "unbiased_choice":
                        served = unbiased
                    elif case == "zeroed_chosen_expert":
                        if not heard:
                            raise SystemExit(f"{case}: run `program` first")
                        at = heard[0][0, compared_positions(spec)]
                        expert = int(np.bincount(at.ravel()).argmax())
                        note = {"zeroed": [0, expert], "read_by": int(
                            (at == expert).any(-1).sum())}
                        judge = broken_leaf(stack, params, reference,
                                            "we_down", (0, expert), None)
                    elif case in check_config.BREAKS:
                        judge = broken_leaf(stack, params, reference,
                                            *check_config.BREAKS[case])
                    result = correctness.check(served, cfg, params, config,
                                               spec, seed, page, judge)
                line = {"model_id": config["model_id"], "tag": args.tag,
                        "device": devices[0].device_kind, "seed": seed,
                        "case": case, "seconds": time.monotonic() - t,
                        "sizes": {k: spec.get(k) for k in (
                            "prefill_tokens", "extend_chunks",
                            "extend_tokens", "decode_steps")},
                        **note, "result": result}
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
            del params
    return 0


def broken_leaf(stack, params, reference, leaf, index, shift):
    """`params[leaf][index]` broken as `check_config.broken` breaks it for
    as long as `stack` is open, and the reference that gets the true leaf
    back for its own pass."""
    true = params[leaf][index] + 0
    stack.enter_context(check_config.swapped(
        params, leaf, index, check_config.broken(true, shift)))

    def forward(params_, hf, ids, **kw):
        with check_config.swapped(params_, leaf, index, true):
            return reference.forward(params_, hf, ids, **kw)

    return like(reference, forward)


def like(reference, forward):
    """`reference` with another `forward`."""
    return types.SimpleNamespace(
        forward=forward, FOLLOWS=getattr(reference, "FOLLOWS", None),
        __name__=reference.__name__)


if __name__ == "__main__":
    sys.exit(main())
