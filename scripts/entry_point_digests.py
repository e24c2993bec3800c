"""sha256 of the jaxpr of every older family's paged entry points (prefill,
extend, decode, verify) at its debug preset, the Pallas kernels traced in
(LLMLB_TPU_ATTENTION=pallas, interpreted), as JSON on stdout: "a change to
shared code left the other families' programs what they were", off the chip.

    cd <parent checkout> && JAX_PLATFORMS=cpu python3 <this file> > a.json
    cd <change>          && JAX_PLATFORMS=cpu python3 <this file> > b.json

and compare the two tables (PR 64: 41 of 41 equal after `_mla_block` took a
gate and an indexer; addresses and paths are stripped before hashing). Run
by no cell and no test; a family added later joins `PRESETS` here."""
import hashlib, json, os, re, sys
os.environ["LLMLB_TPU_ATTENTION"] = "pallas"
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.models import FAMILIES
PRESETS = {"llama": "debug-tiny", "mixtral": "debug-moe-tiny",
           "deepseek_v3": "debug-mla-tiny", "sdar_moe": "debug-sdar-tiny",
           "nemotron_h": "debug-nemotron-h-tiny", "longcat_flash": "debug-longcat-tiny",
           "mimo_v2": "debug-mimo-tiny", "olmo_hybrid": "debug-olmo-hybrid-tiny",
           "afmoe": "debug-trinity-tiny", "granite_hybrid": "debug-granite-hybrid-tiny",
           "lfm2_moe": "debug-lfm2-moe-tiny", "kimi_linear": "debug-kimi-linear-tiny"}
out = {}
for m in FAMILIES:
    name = m.FAMILY.name
    if name not in PRESETS:
        continue
    cfg = get_preset(PRESETS[name])
    params = jax.eval_shape(lambda k: m.init_params(cfg, k), jax.random.PRNGKey(0))
    slotted = m.FAMILY.state_slot_bytes is not None
    kw = {"num_slots": 4} if slotted else {}
    ck, cv = jax.eval_shape(lambda: m.init_kv_pages(cfg, 17, 16, **kw))
    tables = jax.ShapeDtypeStruct((4, 4), jnp.int32)
    ids = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    lens = jax.ShapeDtypeStruct((4,), jnp.int32)
    calls = {
        "prefill_into_pages": lambda p, i, l, t, k, v: m.prefill_into_pages(p, cfg, i, l, t, k, v),
        "prefill_extend_pages": lambda p, i, l, t, k, v: m.prefill_extend_pages(p, cfg, i, l, l, t, k, v),
        "decode_step_paged": lambda p, i, l, t, k, v: m.decode_step_paged(p, cfg, i[:, 0], l, k, v, t, window=64),
    }
    if hasattr(m, "verify_step_paged"):
        calls["verify_step_paged"] = lambda p, i, l, t, k, v: m.verify_step_paged(p, cfg, i[:, :4], l, l, t, k, v, window=64)
    for fn, call in calls.items():
        try:
            text = str(jax.make_jaxpr(call)(params, ids, lens, tables, ck, cv))
        except Exception as e:
            text = f"ERROR {type(e).__name__}: {e}"
        text = re.sub(r"0x[0-9a-f]+", "0x", text)
        text = re.sub(r"/root/[^ \"']*", "", text)
        out[f"{name}.{fn}"] = hashlib.sha256(text.encode()).hexdigest()[:16] + (" ERR " + text[:200] if text.startswith("ERROR") else "")
json.dump(out, sys.stdout, indent=1)
