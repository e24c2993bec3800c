"""The controls the limits of a configuration with GATED DELTA-RULE layers
are set between (`models/olmo_hybrid.py`), beside those of `check_config.py`
(whose loop this repeats): what a state per slot and the rule itself can get
wrong, each as a program that must be refused, and the sound program beside
them. Every result is a JSON line on stdout and in
`chiprun_out/check_linear/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_linear.py --config <file> \
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
  int8_weights       THE PRECISION CONTROL: every MATRIX (the vectors —
                     norms, conv taps, A_log, dt_bias — stay) through int8
                     per output channel and back, in the program's place.
  state_bf16         THE STATE CONTROL: the rule's state rounded to bfloat16
                     after every call, what a bf16 state pool keeps.
  beta_not_doubled   b = sigmoid(.) where the config says 2 sigmoid(.), in
                     the chunked form and in the step.
  no_decay           alpha = 1 (g = 0) in both.
  conv_not_carried   the convolution's carried rows zeroed before each
                     extend: a chunk that convolves as if it began a sequence.

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

from benchmark import check_limits  # noqa: E402

MATRICES = ("embed", "lm_head", "lin_wqkv", "lin_wz", "lin_wab", "lin_wo",
            "wq", "wk", "wv", "wo", "wg", "wu", "wd")
CASES = ("program,interleaved_decode,live_mask_off,int8_weights,state_bf16,"
         "beta_not_doubled,no_decay,conv_not_carried")


def matrices_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place
    (llmlb_tpu/quant's rule for served int8 weights, by name)."""
    import jax

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    trip = jax.jit(lambda w: dequantize_channelwise(
        *quantize_channelwise(w), dtype=w.dtype), donate_argnums=0)
    for name in MATRICES:
        if name in params:
            params[name] = trip(params[name])


@contextlib.contextmanager
def rule_changed(*, g_scale: float = 1.0, beta_scale: float = 1.0):
    """While a program is traced: the log of the decay times `g_scale` and
    the write strength times `beta_scale`, in both forms of the rule."""
    from llmlb_tpu.ops import delta_rule

    real = delta_rule.delta_rule_chunked, delta_rule.delta_rule_step

    def chunked(q, k, v, g, beta, *rest, **kw):
        return real[0](q, k, v, g * g_scale, beta * beta_scale, *rest, **kw)

    def step(pool, layer, q, k, v, alpha, beta, **kw):
        return real[1](pool, layer, q, k, v, alpha ** g_scale,
                       beta * beta_scale, **kw)

    delta_rule.delta_rule_chunked, delta_rule.delta_rule_step = chunked, step
    try:
        yield
    finally:
        delta_rule.delta_rule_chunked, delta_rule.delta_rule_step = real


class Variant:
    """`family` with its three paged serving functions changed: traced
    apart under `patch` (another function than the program jits, so another
    trace cache), the pools passed through `after` behind every call and
    through `before_extend` in front of an extend, and with `step_live` not
    None a decode step over the row in front of every extend.
    (`check_hybrid.Variant` for a family whose entry points take no
    `routing`.)"""

    def __init__(self, family, *, patch=None, after=None, before_extend=None,
                 step_live: bool | None = None):
        self._family = family
        fns = {name: (self._apart(getattr(family, name), patch) if patch
                      else getattr(family, name))
               for name in check_limits.SERVING}

        def served(name):
            def call(params, cfg, *args, **kw):
                args = list(args)
                at = 4  # an extend's pools, behind ids, lens, start, tables
                if name == "prefill_extend_pages":
                    if step_live is not None:
                        args[at:at + 2] = self._step(
                            fns["decode_step_paged"], params, cfg, args,
                            step_live)
                    if before_extend:
                        args[at:at + 2] = before_extend(*args[at:at + 2])
                out = fns[name](params, cfg, *args, **kw)
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
            static_argnames=[n for n in ("cfg", "mesh", "window")
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
        "beta_not_doubled": Variant(family, patch=functools.partial(
            rule_changed, beta_scale=0.5)),
        "no_decay": Variant(family, patch=functools.partial(
            rule_changed, g_scale=0.0)),
        "conv_not_carried": Variant(family, before_extend=rows_forgotten),
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
    out_dir = os.path.join(ROOT, "chiprun_out", "check_linear")
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
                    served_as.get(case, family), cfg, params, config, spec,
                    seed, page, judge)
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
