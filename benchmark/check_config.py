"""The comparison behind `correct` for one configuration file over several
seeds in ONE process, with its controls beside it: the readings a tolerance
is set from (the sound runs' largest, the controls' smallest), and the proof
that a wrong engine is refused. Every result is a JSON line on stdout and in
`chiprun_out/check_config/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_config.py --config <file> \
        --seeds 11,12,13 --sizes '{"prefill_tokens": 32}' \
        --cases program,unfollowed,zeroed_expert,permuted_router

The configuration need not be in `BENCHMARK.json`: this is how a PR reads
its limits before it commits the file. Cases:

  program          the program as it is
  unfollowed       a reference that follows routing, not given the routing:
                   the comparison of before PR 26, which a near tie fails
  zeroed_expert    layer 0, expert 1: `we_down` zeroed on the program's side
  permuted_router  layer 1: the router's columns rotated by one, same side

The broken leaf is swapped in place (donated) and the reference swaps the
true one back for its own pass, so that nothing is held twice: a mixture's
weights fill most of a chip.

A tool for the PR that adds a configuration; the driver does not call it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

BREAKS = {  # case -> (leaf, index, shift): leaf[index] times 0, or rolled
    "zeroed_expert": ("we_down", (0, 1), None),
    "permuted_router": ("router", (1,), 1),
}


def broken(true, shift):
    import jax.numpy as jnp

    return true * 0 if shift is None else jnp.roll(true, shift, axis=-1)


@contextlib.contextmanager
def swapped(params: dict, leaf: str, index: tuple, value):
    """params[leaf][index] is `value` inside the block, in place: the
    stacked array is donated both ways, so nothing is held twice."""
    import jax

    put = jax.jit(lambda w, v: w.at[index].set(v), donate_argnums=0)
    was = params[leaf][index] + 0  # a copy: the array it is part of goes next
    params[leaf] = put(params[leaf], value)
    try:
        yield
    finally:
        params[leaf] = put(params[leaf], was)


def reference_for(case: str, reference, true):
    """The reference as `case` needs it: not told the routing, or given the
    `true` weights back for its own pass where the program's are broken."""
    def forward(params, hf, ids, **kw):
        if case == "unfollowed":
            kw.pop("follow", None)
        with (swapped(params, *BREAKS[case][:2], true) if case in BREAKS
              else contextlib.nullcontext()):
            return reference.forward(params, hf, ids, **kw)

    return types.SimpleNamespace(
        forward=forward, FOLLOWS=getattr(reference, "FOLLOWS", None),
        __name__=reference.__name__)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--base", default=ROOT,
                    help="the directory of the manifest, for its references")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--cases", default="program")
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
    out_dir = os.path.join(ROOT, "chiprun_out", "check_config")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, config["model_id"] + ".jsonl"), "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            params = launcher.make_params(family, cfg, seed, mesh)
            for case in args.cases.split(","):
                t = time.monotonic()
                with contextlib.ExitStack() as stack:
                    true = None
                    if case in BREAKS:
                        leaf, index, shift = BREAKS[case]
                        true = params[leaf][index] + 0
                        stack.enter_context(swapped(
                            params, leaf, index, broken(true, shift)))
                    result = correctness.check(
                        family, cfg, params, config, spec, seed,
                        int(config["engine"].get("kv_page_size", 128)),
                        reference_for(case, reference, true))
                line = {"model_id": config["model_id"], "tag": args.tag,
                        "device": devices[0].device_kind, "seed": seed,
                        "case": case, "seconds": time.monotonic() - t,
                        "sizes": {k: spec.get(k) for k in (
                            "prefill_tokens", "extend_chunks",
                            "extend_tokens", "decode_steps")},
                        "result": result}
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
