"""Bring one model family up on the chip outside the benchmark's launcher and
the engine: seeded weights of a configuration file (or of a preset), one
prefill per row, then bursts of decode steps under the profiler, and the
device's time by operation.

    chiprun -- python3 scripts/chip_family_step.py \
        --config benchmark/configs/kanana-2-30b-a3b-l8.json --rows 64 \
        --context 600 --bursts 6

Prints one JSON object: seconds per decode step (host clock, the bursts
after the first), the device's busy share of the traced bursts and its 40
largest operations (benchmark/trace.py's reduction), and what stopping,
reading and reducing the trace cost (`trace_cost_s`). For reproducing a
failure or reading a step's breakdown without gateway, scheduler or
traffic; a CPU run (JAX_PLATFORMS=cpu, a preset) only shows that it runs.
`--extend N` profiles N extend chunks of 512 tokens a row behind the context
instead (`step_s` is then seconds a TOKEN of a chunk): where a chunk's
device time goes, e.g. `--config benchmark/configs/dots3-note-prev-l5.json
--rows 1 --context 6144 --extend 8` (PERF.md §5, PR 65).
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", help="a benchmark configuration file")
    ap.add_argument("--preset", help="or a preset of engine/presets.py")
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--context", type=int, default=600,
                    help="tokens each row holds when decode starts")
    ap.add_argument("--burst", type=int, default=8)
    ap.add_argument("--bursts", type=int, default=6)
    ap.add_argument("--page-size", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--extend", type=int, default=0, metavar="CHUNKS",
                    help="profile this many extend chunks of 512 tokens a "
                         "row behind the context instead of decode bursts "
                         "(at most eight rows)")
    ap.add_argument("--timeline", type=int, default=0,
                    help="also list this many consecutive device operations "
                         "from the middle of the trace: [label, start us, "
                         "duration us]; the gaps are the device waiting")
    args = ap.parse_args()

    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    configure_compile_cache()
    devices = resolve_backend()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import launcher, trace as trace_mod
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.models import family_for
    from llmlb_tpu.parallel.mesh import build_mesh

    if args.config:
        with open(args.config) as f:
            cfg = launcher.build_cfg(json.load(f))
    else:
        cfg = get_preset(args.preset)
    family = family_for(cfg)
    mesh = build_mesh(launcher.mesh_config_for(cfg, 1), devices=devices[:1])
    params = launcher.make_params(family, cfg, args.seed, mesh)

    ps, rows, k = args.page_size, args.rows, args.burst
    total = args.context + max(k * (args.bursts + 1), 512 * (args.extend + 1))
    ppn = -(-total // ps)
    # a family with state per slot (models/nemotron_h.py): row i is slot i
    slotted = family.FAMILY.state_slot_bytes is not None
    cache_k, cache_v = family.init_kv_pages(
        cfg, rows * ppn + 1, ps, **({"num_slots": rows} if slotted else {}))
    tables = jnp.arange(1, rows * ppn + 1, dtype=jnp.int32).reshape(rows, ppn)
    rng = np.random.default_rng(args.seed)
    chunk = min(args.context, 512)
    ids = jnp.asarray(rng.integers(8, cfg.vocab_size, (rows, args.context)),
                      jnp.int32)
    slots = {}
    for lo in range(0, rows, 8):  # prefill in groups of eight, chunk by chunk
        sl = slice(lo, lo + 8)
        n = ids[sl].shape[0]
        slots = ({"slot_ids": jnp.arange(lo, lo + n, dtype=jnp.int32)}
                 if slotted else {})
        _, cache_k, cache_v, *_ = family.prefill_into_pages(
            params, cfg, ids[sl, :chunk], jnp.full((n,), chunk, jnp.int32),
            tables[sl], cache_k, cache_v, **slots)
        for at in range(chunk, args.context, chunk):
            t = min(chunk, args.context - at)
            _, cache_k, cache_v, *_ = family.prefill_extend_pages(
                params, cfg, ids[sl, at:at + t], jnp.full((n,), t, jnp.int32),
                jnp.full((n,), at, jnp.int32), tables[sl], cache_k, cache_v,
                **slots)
    window = ppn * ps

    def burst(params, last, lens, cache_k, cache_v, tables):
        def body(carry, _):
            last, lens, ck, cv = carry
            logits, ck, cv, *stats = family.decode_step_paged(
                params, cfg, last, lens, ck, cv, tables, window=window,
                live=jnp.ones((rows,), bool))
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1, ck,
                    cv), stats

        return jax.lax.scan(body, (last, lens, cache_k, cache_v), None,
                            length=k)

    def chunks(params, last, lens, cache_k, cache_v, tables):
        """One extend chunk of 512 random tokens a row behind `lens`, under
        `burst`'s signature (its `k` is then the chunk's tokens)."""
        more = jnp.asarray(rng.integers(8, cfg.vocab_size, (rows, 512)),
                           jnp.int32)
        logits, cache_k, cache_v, *stats = family.prefill_extend_pages(
            params, cfg, more, jnp.full((rows,), 512, jnp.int32), lens,
            tables, cache_k, cache_v, **slots)
        return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 512,
                cache_k, cache_v), stats

    if args.extend:  # the rows of the last prefill group: all of them
        assert rows <= 8, "--extend takes one prefill group of rows"
        step, k, args.bursts = chunks, 512, args.extend
    else:
        step = jax.jit(burst, donate_argnums=(3, 4))
    last = ids[:, -1]
    lens = jnp.full((rows,), args.context, jnp.int32)
    (last, lens, cache_k, cache_v), stats = step(params, last, lens, cache_k,
                                                 cache_v, tables)
    jax.block_until_ready(last)  # compiled, and one burst run
    trace_dir = tempfile.mkdtemp(prefix="family-step-")
    jax.profiler.start_trace(trace_dir)
    t0 = time.monotonic()
    for _ in range(args.bursts):
        (last, lens, cache_k, cache_v), stats = step(
            params, last, lens, cache_k, cache_v, tables)
    jax.block_until_ready(last)
    seconds = time.monotonic() - t0
    jax.profiler.stop_trace()
    stop_s = time.monotonic() - t0 - seconds
    found = [os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    out = {"device": devices[0].device_kind, "rows": rows,
           "context_at_end": int(lens[0]), "step_s": seconds / (args.bursts * k),
           "counters_last_burst": jax.tree.map(
               lambda a: np.asarray(a).sum(0).tolist(), stats)}
    if found:
        t1 = time.monotonic()
        profile = jax.profiler.ProfileData.from_file(sorted(found)[-1])
        t2 = time.monotonic()
        red = trace_mod.reduce(profile, window_s=seconds, top=40)
        # what the benchmark's traced window pays behind its stop (the
        # launcher does the same three things under a client's 240 s)
        out["trace_cost_s"] = {
            "stop_trace": stop_s, "read": t2 - t1,
            "reduce": time.monotonic() - t2,
            "device_events": sum(v["count"] for v in red["ops"].values())}
        if args.timeline:
            plane = trace_mod.device_planes(profile)[0]
            events = trace_mod._events(trace_mod._line(plane, trace_mod.OPS_LINE))
            mid = events[len(events) // 2:][:args.timeline]
            out["timeline"] = [[trace_mod.op_label(name),
                                round((s - mid[0][0]) * 1e6, 1),
                                round((e - s) * 1e6, 1)] for s, e, name in mid]
        if red.get("busy_s") is not None:
            out["busy_share"] = red["busy_s"] / seconds
            out["device_ops"] = red["breakdown"]["device_ops"]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
