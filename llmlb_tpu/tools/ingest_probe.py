"""Model-ingestion probe: validate a checkpoint before serving it.

TPU equivalent of the reference's native ingestion POCs (SURVEY.md §2.3 #2-3:
the safetensors reader that checks shard integrity and known-bad tensors, and
the ONNX session probe that proves a checkpoint loads into a runtime). Here
the probe:

  1. walks every safetensors shard with the C++ mmap reader (falling back to
     pure-Python parsing), checking header integrity, dtype support, NaN/Inf
     contamination, and per-shard tensor counts;
  2. cross-checks tensor names/shapes against the architecture config
     (config.json) the serving engine would build;
  3. optionally lowers the model's prefill step to StableHLO — proof the
     checkpoint's architecture actually compiles for the target — and emits
     a machine-readable metadata report.

Usage: python -m llmlb_tpu.tools.ingest_probe CHECKPOINT_DIR [--stablehlo OUT]
Exit code 0 = servable; 1 = validation findings; 2 = unreadable.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os

import numpy as np


@dataclasses.dataclass
class ProbeReport:
    checkpoint: str
    shards: list[dict] = dataclasses.field(default_factory=list)
    tensor_count: int = 0
    total_bytes: int = 0
    dtypes: dict = dataclasses.field(default_factory=dict)
    findings: list[str] = dataclasses.field(default_factory=list)
    config: dict | None = None
    stablehlo_bytes: int | None = None

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> dict:
        return {**dataclasses.asdict(self), "ok": self.ok}


_SUPPORTED_DTYPES = {"F32", "F16", "BF16", "I32", "I64", "U8", "I8"}


def _iter_shard_tensors(path: str):
    """Yield (name, dtype_str, shape, np_array_or_None) per tensor. Uses the
    native mmap reader when built; otherwise parses the safetensors header
    in Python (header-only: no data validation on the fallback path). The
    fallback only engages when the native reader failed before yielding
    anything — a mid-iteration native failure must propagate rather than
    restart the walk and double-count tensors already yielded."""
    yielded = False
    try:
        from llmlb_tpu.native import NativeSafetensors

        st = NativeSafetensors(path)
        try:
            for name in st.keys():
                arr = st.get_tensor(name)
                yielded = True
                yield name, str(arr.dtype), tuple(arr.shape), arr
        finally:
            st.close()
        return
    except Exception:
        if yielded:
            raise
    # pure-python header walk
    import struct

    with open(path, "rb") as f:
        (hlen,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(hlen))
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        yield name, meta.get("dtype", "?"), tuple(meta.get("shape", ())), None


def probe_checkpoint(model_dir: str, *, sample_values: bool = True,
                     stablehlo_out: str | None = None) -> ProbeReport:
    report = ProbeReport(checkpoint=os.path.abspath(model_dir))
    shards = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not shards:
        report.findings.append("no .safetensors shards found")
        return report

    seen: dict[str, tuple] = {}
    for path in shards:
        shard_info = {"file": os.path.basename(path),
                      "bytes": os.path.getsize(path), "tensors": 0}
        try:
            for name, dtype, shape, arr in _iter_shard_tensors(path):
                shard_info["tensors"] += 1
                report.tensor_count += 1
                report.dtypes[dtype] = report.dtypes.get(dtype, 0) + 1
                if name in seen:
                    report.findings.append(
                        f"duplicate tensor {name!r} (also in {seen[name][0]})"
                    )
                seen[name] = (os.path.basename(path), shape)
                if arr is None:  # header-only path: safetensors dtype string
                    bad_dtype = dtype.upper() not in _SUPPORTED_DTYPES
                else:
                    # native path: numpy dtype string. bfloat16 comes from
                    # ml_dtypes, for which np.issubdtype(.., np.number) is
                    # False — but it is the dominant LLM checkpoint dtype.
                    try:
                        bad_dtype = not (
                            str(dtype) == "bfloat16"
                            or np.issubdtype(np.dtype(dtype), np.number)
                        )
                    except TypeError:
                        bad_dtype = True
                if bad_dtype:
                    report.findings.append(
                        f"{name}: unsupported dtype {dtype}"
                    )
                if arr is not None and sample_values and arr.size:
                    flat = arr.reshape(-1)
                    # bounded sample: checking multi-GB tensors fully would
                    # defeat the point of an mmap probe. bfloat16 counts as
                    # floating even though np.issubdtype says otherwise.
                    is_float = (str(arr.dtype) == "bfloat16"
                                or np.issubdtype(arr.dtype, np.floating))
                    sample = np.asarray(
                        flat[:: max(1, flat.size // 4096)][:8192],
                        np.float32,
                    ) if is_float else None
                    if sample is not None and not np.isfinite(sample).all():
                        report.findings.append(
                            f"{name}: non-finite values (NaN/Inf) in shard "
                            f"{os.path.basename(path)}"
                        )
        except Exception as e:
            report.findings.append(
                f"{os.path.basename(path)}: unreadable ({e})"
            )
        report.total_bytes += shard_info["bytes"]
        report.shards.append(shard_info)

    # index coverage: every tensor the index names must exist
    index_path = os.path.join(model_dir, "model.safetensors.index.json")
    if os.path.isfile(index_path):
        try:
            with open(index_path) as f:
                weight_map = json.load(f).get("weight_map", {})
            missing = [t for t in weight_map if t not in seen]
            if missing:
                report.findings.append(
                    f"{len(missing)} tensors in the index are missing from "
                    f"shards (first: {missing[0]})"
                )
        except (OSError, ValueError) as e:
            report.findings.append(f"unreadable shard index: {e}")

    # architecture cross-check + optional StableHLO lowering
    config_path = os.path.join(model_dir, "config.json")
    if os.path.isfile(config_path):
        try:
            from llmlb_tpu.engine.weights import load_config

            cfg = load_config(model_dir)
            report.config = {
                "num_layers": cfg.num_layers,
                "hidden_size": cfg.hidden_size,
                "num_heads": cfg.num_heads,
                "num_kv_heads": cfg.num_kv_heads,
                "vocab_size": cfg.vocab_size,
                "max_position_embeddings": cfg.max_position_embeddings,
            }
            expected = cfg.num_layers
            found_layers = len({
                name.split(".")[2] for name in seen
                if name.startswith("model.layers.")
            })
            if found_layers and found_layers != expected:
                report.findings.append(
                    f"config says {expected} layers but shards carry "
                    f"{found_layers}"
                )
            if stablehlo_out is not None:
                report.stablehlo_bytes = _emit_stablehlo(cfg, stablehlo_out)
        except Exception as e:
            report.findings.append(f"config/arch check failed: {e}")
    else:
        report.findings.append("no config.json (cannot cross-check arch)")
    return report


def _emit_stablehlo(cfg, out_path: str) -> int:
    """Lower the prefill step to StableHLO text — proof the architecture
    compiles for the serving path (the ONNX-probe equivalent)."""
    import jax
    import jax.numpy as jnp

    from llmlb_tpu.models import family_for

    family = family_for(cfg)
    params = family.init_params(cfg, jax.random.PRNGKey(0))
    # two 16-token pages behind page 0 (the trash page), one row owning both
    ck, cv = family.init_kv_pages(cfg, 3, 16)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    ids = jnp.zeros((1, 16), jnp.int32)
    lens = jnp.full((1,), 16, jnp.int32)

    lowered = jax.jit(
        lambda p, i, n, t, k, v: family.prefill_into_pages(
            p, cfg, i, n, t, k, v)[0]
    ).lower(params, ids, lens, tables, ck, cv)
    text = lowered.as_text()
    with open(out_path, "w") as f:
        f.write(text)
    return len(text)


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m llmlb_tpu.tools.ingest_probe",
        description="Validate a checkpoint before serving it.",
    )
    parser.add_argument("checkpoint_dir")
    parser.add_argument(
        "--stablehlo", metavar="OUT",
        help="also lower the prefill step to StableHLO text at OUT",
    )
    args = parser.parse_args(argv)
    if not os.path.isdir(args.checkpoint_dir):
        print(json.dumps({"error": f"not a directory: {args.checkpoint_dir}"}))
        return 2
    report = probe_checkpoint(args.checkpoint_dir, stablehlo_out=args.stablehlo)
    print(json.dumps(report.to_json(), indent=2))
    return 0 if report.ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
