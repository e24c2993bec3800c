"""What the chip's compiler materializes in a configuration's decode program,
read off the chip: every result of at least `--min-mb` MB with its shape,
layout, memory space and the source operation it came from.

    python3 scripts/program_temporaries.py \
        --config benchmark/configs/mistral-7b-l16.json --set num_hidden_layers=2

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached (`topologies.get_topology_desc`); nothing runs, so
this says nothing of times. It builds the configuration's family with the
shapes of its weights and of a pool, compiles `--steps` decode steps under a
scan (a block family: one block pass two blocks wide) with the kernels
lowered through Mosaic, and prints one line a large result, largest first,
then the compiler's own count of temporary bytes (`--chunk N [--extend]`:
the prefill, or the extend, of rows x N tokens instead). A weight- or pool-shaped
line in a layout other than the stored one (`{2,1,0}` for a `[L, E, N]`
stack) is a copy the program makes every step: PR 31's experts, PR 45's
whole-pool copy and the transposed slices of `wq`, `wk`, `wv` that PR 47
removed were all found here before a chip run. Run by no cell and no test.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
               "u64": 8}
# one array of a result: type, dimensions, and the layout behind it if any
ARRAY = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")
MOVES_NOTHING = ("parameter", "get-tuple-element", "tuple", "bitcast", "while",
                 "conditional")
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")


def large_results(hlo: str, min_bytes: int) -> list[dict]:
    """One record an array of at least `min_bytes` that an instruction of
    `hlo` (a compiled module's text) gives, parameters and the pieces of a
    tuple taken apart aside."""
    out = []
    # a fusion's body describes one pass over its operands: nothing in it is
    # written to memory but the fusion's own result
    fused = set(re.findall(r" fusion\(.*calls=(%[\w.\-]+)", hlo))
    inside = None  # the computation a line belongs to
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1)
        m = INSTRUCTION.match(line)
        if not m or inside in fused or m.group(3) in MOVES_NOTHING:
            continue
        _, result, op, rest = m.groups()
        source = re.search(r'op_name="([^"]*)"', rest)
        kind = re.search(r"kind=(\w+)", rest)
        for dtype, dims, layout in ARRAY.findall(result):
            if dtype not in DTYPE_BYTES:
                continue
            shape = [int(d) for d in dims.split(",") if d]
            size = DTYPE_BYTES[dtype] * math.prod(shape)
            if size < min_bytes:
                continue
            layout = (layout or "{}")[1:-1]
            order, _, tiling = layout.partition(":")
            space = re.search(r"S\((\d+)\)", tiling)
            out.append({
                "mb": round(size / 1e6, 2), "shape": f"{dtype}[{dims}]",
                "layout": "{" + order + "}",
                "memory_space": int(space.group(1)) if space else 0,
                "op": op + (f":{kind.group(1)}" if kind else ""),
                "op_name": source.group(1) if source else ""})
    return sorted(out, key=lambda r: -r["mb"])


def compile_decode(config: dict, *, rows: int, pages: int, page_size: int,
                   table: int, steps: int, chunk: int = 0,
                   extend: bool = False):
    """The configuration's decode program, compiled for one described v5e;
    with `chunk` its prefill of `rows` x `chunk` tokens instead, or with
    `extend` too its extend of such a chunk."""
    # before jax is imported: the backend here is the CPU, and the compiler
    # is told which chip it describes (else it warns, and logs under /tmp)
    for name, value in (("JAX_PLATFORMS", "cpu"), ("TPU_LOG_DIR", "disabled"),
                        ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                        ("TPU_WORKER_HOSTNAMES", "localhost")):
        os.environ.setdefault(name, value)
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import launcher
    from llmlb_tpu.models import family_for
    from llmlb_tpu.ops import pallas_attention, pallas_moe, ssm

    # the backend of this process is the CPU; the program read here is the
    # chip's, so the kernels lower through Mosaic and not the interpreter
    os.environ["LLMLB_TPU_ATTENTION"] = "pallas"
    for module in (pallas_attention, pallas_moe, ssm):
        module._interpret_default = lambda: False

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    cfg = launcher.build_cfg(config)
    family = family_for(cfg)
    slotted = family.FAMILY.state_slot_bytes is not None
    params = on_chip(jax.eval_shape(
        functools.partial(family.init_params, cfg), jax.random.PRNGKey(0)))
    pools = on_chip(jax.eval_shape(lambda: family.init_kv_pages(
        cfg, pages, page_size, **({"num_slots": rows} if slotted else {}))))
    ints = on_chip(jax.ShapeDtypeStruct((rows,), jnp.int32))
    live = on_chip(jax.ShapeDtypeStruct((rows,), jnp.bool_))
    tables = on_chip(jax.ShapeDtypeStruct((rows, table), jnp.int32))
    window = table * page_size
    block = family.FAMILY.block_length(cfg)
    with jax.default_matmul_precision("default"):
        if chunk:
            ids = on_chip(jax.ShapeDtypeStruct((rows, chunk), jnp.int32))
            slots = {"slot_ids": ints} if slotted else {}
            if extend:
                return cfg, family.prefill_extend_pages.lower(
                    params, cfg, ids, ints, ints, tables, *pools, None,
                    **slots).compile()
            return cfg, family.prefill_into_pages.lower(
                params, cfg, ids, ints, tables, *pools, None,
                **slots).compile()
        if block > 1:  # the scheduler's pass: two blocks wide, one's logits
            ids = on_chip(jax.ShapeDtypeStruct((rows, 2 * block), jnp.int32))
            return cfg, family.verify_step_paged.lower(
                params, cfg, ids, ints, ints, tables, *pools, None,
                window=window, logits_from=ints, logits_len=block).compile()

        def burst(params, last, lens, cache_k, cache_v, tables, live):
            def body(carry, _):
                last, lens, ck, cv = carry
                logits, ck, cv, *counters = family.decode_step_paged(
                    params, cfg, last, lens, ck, cv, tables, window=window,
                    live=live)
                return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1,
                        ck, cv), counters

            return jax.lax.scan(body, (last, lens, cache_k, cache_v), None,
                                length=steps)

        return cfg, jax.jit(burst, donate_argnums=(3, 4)).lower(
            params, ints, ints, *pools, tables, live).compile()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a benchmark configuration file (a config.json)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the file, e.g. a depth of 2")
    ap.add_argument("--rows", type=int,
                    help="default: the file's engine.num_slots, else 32")
    ap.add_argument("--pages", type=int,
                    help="default: the file's engine.kv_pages, else 544")
    ap.add_argument("--page-size", type=int, default=128)
    ap.add_argument("--table", type=int, default=16,
                    help="pages a row's block table holds")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=0,
                    help="compile the prefill of rows x CHUNK tokens instead")
    ap.add_argument("--extend", action="store_true",
                    help="with --chunk: the extend of such a chunk")
    ap.add_argument("--min-mb", type=float, default=1.0)
    ap.add_argument("--hlo", help="also write the compiled module's text here")
    args = ap.parse_args()

    with open(args.config) as f:
        config = json.load(f)
    for item in args.set:
        key, _, value = item.partition("=")
        config[key] = json.loads(value)
    engine = config.get("engine", {})
    rows = args.rows or engine.get("num_slots", 32)
    cfg, compiled = compile_decode(
        config, rows=rows, pages=args.pages or engine.get("kv_pages", 544),
        page_size=args.page_size, table=args.table, steps=args.steps,
        chunk=args.chunk, extend=args.extend)
    hlo = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    print(f"# {type(cfg).__name__}, {cfg.num_layers} layers, {rows} rows")
    print("# MB  shape  layout  memory_space  op  op_name")
    for r in large_results(hlo, int(args.min_mb * 1e6)):
        print(f"{r['mb']:10.2f}  {r['shape']}  {r['layout']}  "
              f"S({r['memory_space']})  {r['op']}  {r['op_name']}")
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"# temporaries: {temporaries / 1e6:.1f} MB; kernels: {kernels}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
