"""The controls the limits of a configuration with STATE-SPACE layers are
set between, beside those of `check_config.py` and `check_limits.py` (whose
loop this repeats): what a recurrent state per slot can get wrong, each as a
program that must be refused, and the sound program beside them. Every
result is a JSON line on stdout and in
`chiprun_out/check_hybrid/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_hybrid.py --config <file> \
        --seeds 11,12,13 [--cases program,state_bf16,...]

Cases:

  program            the program as it is
  interleaved_decode SOUND, and must pass as `program` does: before each
                     extend call a decode step runs over the row with `live`
                     false, as the engine's burst steps a slot that is
                     mid-way through a chunked prefill. The state must not
                     move.
  live_mask_off      THE MASK CONTROL: the same step with `live` true — the
                     burst advances the prefilling row's state by a token
                     that is not the sequence's.
  int8_weights       THE PRECISION CONTROL, as `check_limits.py` has it, over
                     the MATRICES (the vectors — norms, conv taps, A_log,
                     dt_bias, D, the choice bias — stay): each through int8
                     per output channel and back.
  state_bf16         THE STATE CONTROL: the recurrent state rounded to
                     bfloat16 after every call, what a bf16 state pool keeps.
  no_decay           exp(dt A) left out of the recurrence (A = 0) in the
                     chunked scan and in the decode step.
  conv_not_carried   the convolution's carried rows zeroed before each
                     extend: a chunk that convolves as if it began a sequence.
  unfollowed, unbiased_choice, zeroed_chosen_expert
                     as `check_config.py` and `check_limits.py` have them; the
                     zeroed expert is the HELD expert the compared positions
                     chose most in the first expert layer.

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

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import check_config, check_limits  # noqa: E402

MATRICES = ("embed", "lm_head", "ssm_in", "ssm_out", "wq", "wk", "wv", "wo",
            "router", "we_up", "we_down", "ws_up", "ws_down")
OUTPUT_MAJOR = ("we_up",)  # stored [.., out, in]: the contraction is last
CASES = ("program,interleaved_decode,live_mask_off,int8_weights,state_bf16,"
         "no_decay,conv_not_carried,unfollowed,unbiased_choice,"
         "zeroed_chosen_expert")


def matrices_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place
    (check_limits.rounded_to_int8's rule, by name)."""
    import jax
    import jax.numpy as jnp

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    def trip(w, swap):
        v = jnp.swapaxes(w, -1, -2) if swap else w
        v = dequantize_channelwise(*quantize_channelwise(v), dtype=w.dtype)
        return jnp.swapaxes(v, -1, -2) if swap else v

    jitted = jax.jit(trip, static_argnums=1, donate_argnums=0)
    for name in MATRICES:
        if name in params:
            params[name] = jitted(params[name], name in OUTPUT_MAJOR)


@contextlib.contextmanager
def no_decay():
    """While a program is traced: A = 0 in both forms of the recurrence."""
    from llmlb_tpu.ops import ssm

    real = ssm.ssd_chunked, ssm.ssm_step
    ssm.ssd_chunked = lambda x, dt, a, *r, **kw: real[0](x, dt, a * 0, *r, **kw)
    ssm.ssm_step = lambda x, dt, a, *r, **kw: real[1](x, dt, a * 0, *r, **kw)
    try:
        yield
    finally:
        ssm.ssd_chunked, ssm.ssm_step = real


class Variant:
    """`family` with its three paged serving functions changed: traced
    apart under `patch` (another function than the program jits, so another
    trace cache), the pools passed through `after` behind every call and
    through `before_extend` in front of an extend, and with `step_live` not
    None a decode step over the row in front of every extend."""

    def __init__(self, family, *, patch=None, after=None, before_extend=None,
                 step_live: bool | None = None):
        self._family = family
        fns = {name: (self._apart(getattr(family, name), patch) if patch
                      else getattr(family, name))
               for name in check_limits.SERVING}

        def served(name):
            def call(params, cfg, *args, routing: bool = False, **kw):
                args = list(args)
                at = 4  # an extend's pools, behind ids, lens, start, tables
                if name == "prefill_extend_pages":
                    if step_live is not None:
                        args[at:at + 2] = self._step(
                            fns["decode_step_paged"], params, cfg, args,
                            step_live)
                    if before_extend:
                        args[at:at + 2] = before_extend(*args[at:at + 2])
                out = fns[name](params, cfg, *args, routing=routing, **kw)
                if after:
                    out = (out[0], *after(out[1], out[2]), *out[3:])
                return out

            return call

        for name in check_limits.SERVING:
            setattr(self, name, served(name))

    def __getattr__(self, name):
        return getattr(self._family, name)

    @staticmethod
    def _step(decode, params, cfg, extend_args, live: bool):
        """One decode step over the extend call's rows, its pools returned:
        a token that is not the sequence's, at the rows' lengths."""
        import jax.numpy as jnp

        ids, _lens, start, tables, ck, cv = extend_args[:6]
        rows = ids.shape[0]
        window = tables.shape[1] * ck.pages.shape[2]
        _, ck, cv, *_ = decode(
            params, cfg, jnp.full((rows,), 9, jnp.int32), start, ck, cv,
            tables, None, window=window, live=jnp.full((rows,), live))
        return ck, cv

    @staticmethod
    def _apart(fn, patch):
        import jax

        body = fn.__wrapped__  # under the program's jax.jit
        names = inspect.signature(body).parameters

        @functools.wraps(body)
        def traced_apart(*args, **kw):
            with patch():
                return body(*args, **kw)

        return jax.jit(
            traced_apart,
            static_argnames=[n for n in ("cfg", "mesh", "window", "routing")
                             if n in names],
            donate_argnames=("cache_k", "cache_v"))


def variants(family) -> dict:
    import jax.numpy as jnp

    def state_to_bf16(ck, cv):
        return ck._replace(state=ck.state.astype(jnp.bfloat16)
                           .astype(ck.state.dtype)), cv

    def rows_forgotten(ck, cv):
        return ck, cv._replace(state=cv.state * 0)

    return {
        "interleaved_decode": Variant(family, step_live=False),
        "live_mask_off": Variant(family, step_live=True),
        "state_bf16": Variant(family, after=state_to_bf16),
        "no_decay": Variant(family, patch=no_decay),
        "conv_not_carried": Variant(family, before_extend=rows_forgotten),
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
    first, held = cfg.held_experts
    page = int(config["engine"].get("kv_page_size", 128))
    out_dir = os.path.join(ROOT, "chiprun_out", "check_hybrid")
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
                        note = {"zeroed": [0, expert], "read_by": int(
                            (at == first + expert).any(-1).sum())}
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
