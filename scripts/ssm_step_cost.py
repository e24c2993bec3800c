"""What a (row, layer) costs the state-space step kernel, alone on the chip:
no model, no engine, no scheduler.

    chiprun -- python3 scripts/ssm_step_cost.py [--groups 8 --layers 6]

`ops/ssm.ssm_decode_step` at the two shapes the cells run it at, side by
side in one call: Granite-4.0-H-Micro's (64 heads of 64 channels, ONE group
of B and C, a state of 128: 2.097 MB a row and layer in float32) over a pool
[36, 32, 64, 64, 128], every row live: a program of 36 calls one after
another, a call a layer, each taking the pool the last one left (as a decode
step's state-space layers do); then Nemotron-3-Nano's cut, EIGHT groups over
6 layers. Both move the same bytes a (row, layer), so `eight_over_one` (the
ratio of their `device_us_a_row_layer`) says what the groups cost the body.
`--groups G --layers L` times that one shape alone. Two clocks, as
`scripts/delta_step_cost.py` has them: the device's own (the kernel's
events on the profiler's "XLA Ops" line, `device_us` a call) and the host's
around the whole program (`wall_us` a call, with the operands' fusions
between two calls). Beside them µs a (row, layer) and what its bytes take
at the published bandwidth: the state read once and written once, 2 x
2.097 MB = 4.19 MB = 5.1 µs at 819 GB/s (a kernel that only copies a slot
through the same pipeline reads 6.4: PERF.md section 6, PR 56). Prints one
JSON object (`shapes`: one entry a shape) and writes it to
`chiprun_out/ssm_step_cost.json`. On the CPU (`JAX_PLATFORMS=cpu`) it runs
the interpreter at a small size and says so: a rehearsal of the script, not
a number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.peaks import PEAKS  # noqa: E402
from scripts.decode_page_cost import _device_us  # noqa: E402

HEADS, CHANNELS, STATE, ROWS = 64, 64, 128, 32
HBM_BYTES_PER_S = PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
# (groups, layers): granite-4.0-h-micro's, nemotron-3-nano-30b-a3b-l14's
SHAPES = ((1, 36), (8, 6))


def measure(groups: int, layers: int, reps: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from llmlb_tpu.ops import ssm

    small = jax.default_backend() != "tpu"
    heads, layers, rows = (8, 2, 4) if small else (HEADS, layers, ROWS)
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 6)
    dtx = jax.random.normal(keys[0], (rows, heads, CHANNELS), jnp.float32)
    decay = jax.random.uniform(keys[1], (rows, heads), jnp.float32, 0.5, 1.0)
    b, c = (jax.random.normal(key, (rows, groups, STATE), jnp.float32)
            for key in keys[2:4])
    pool = jax.random.normal(keys[4], (layers, rows, heads, CHANNELS, STATE),
                             jnp.float32)

    @jax.jit
    def program(pool, decay, dtx, b, c):
        out = 0.0
        for layer in range(layers):
            # each call's operands hang on the last one's output
            pool, y = ssm.ssm_decode_step(pool, layer, decay, dtx + out, b, c)
            out = y * 1e-3
        return pool, out

    operands = (decay, dtx, b, c)
    pool, out = program(pool, *operands)
    jax.block_until_ready(out)  # compiled, and run once
    trace_dir = tempfile.mkdtemp(prefix="ssm-step-")
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(reps):
        pool, out = program(pool, *operands)
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()

    state_bytes = 2 * heads * CHANNELS * STATE * 4
    result = {
        "device": jax.devices()[0].device_kind, "rehearsal": small,
        "heads": heads, "channels": CHANNELS, "state": STATE,
        "groups": groups, "layers": layers, "rows": rows, "reps": reps,
        "wall_us": wall / (reps * layers) * 1e6,
        "state_bytes_a_row_layer": state_bytes,
        "roofline_us_a_row_layer": state_bytes / HBM_BYTES_PER_S * 1e6,
    }
    traced = _device_us(trace_dir, "ssm_decode_step")
    if traced:
        result["device_us"], result["events"] = traced
        result["device_us_a_row_layer"] = traced[0] / rows
        result["roofline_share_pct"] = (
            100.0 * result["roofline_us_a_row_layer"]
            / result["device_us_a_row_layer"])
    result["wall_us_a_row_layer"] = result["wall_us"] / rows
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, help="one shape alone: its groups")
    ap.add_argument("--layers", type=int, help="and its layers (default 36)")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    shapes = (SHAPES if args.groups is None and args.layers is None
              else ((args.groups or 1, args.layers or 36),))
    result = {"shapes": [measure(groups, layers, args.reps, args.seed)
                         for groups, layers in shapes]}
    times = [r.get("device_us_a_row_layer") for r in result["shapes"]]
    if len(times) == 2 and all(times):
        result["eight_over_one"] = times[1] / times[0]
    print(json.dumps(result))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "ssm_step_cost.json"), "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
