"""Multi-host serving: jax.distributed bring-up + DCN-aware hybrid meshes.

The reference's distributed story is HTTP between gateway and runtimes
(SURVEY.md §2.4: "no NCCL/MPI/Gloo anywhere") — multi-host model execution is
TPU-native new design. The shape of it:

- Each host (TPU slice worker) runs one engine process; `init_from_env()`
  brings up `jax.distributed` so all processes see one global device set.
- `build_hybrid_mesh()` lays DCN-crossing axes (dp replicas, ep experts)
  OUTSIDE the ICI axes (sp, tp), so latency-critical collectives (tp
  all-reduce every layer, sp ring ppermute) ride ICI and only
  high-arithmetic-intensity or per-request work crosses DCN — the
  BASELINE.json config #5 (Mixtral multi-slice) layout.
- On real multi-slice TPU, device "slices" drive the DCN grouping; in the
  CPU simulation used by tests and the driver dry-run, process boundaries
  stand in for slices (`process_is_granule`).

Spawned 2-host CPU simulation: `python -m llmlb_tpu.parallel.distributed
--selftest` (used by __graft_entry__.dryrun_multichip).
"""

from __future__ import annotations

import logging
import os

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from llmlb_tpu.parallel.mesh import MeshConfig

log = logging.getLogger("llmlb_tpu.parallel.distributed")


def init_from_env() -> bool:
    """Initialize jax.distributed from LLMLB_* env (returns True if it ran).

    LLMLB_COORDINATOR=host:port, LLMLB_NUM_HOSTS, LLMLB_HOST_ID configure the
    cluster explicitly; on Cloud TPU pods, calling with no variables set but
    LLMLB_DISTRIBUTED=1 lets JAX autodetect from the TPU metadata. Must run
    before the first backend use."""
    coordinator = os.environ.get("LLMLB_COORDINATOR")
    num_hosts = int(os.environ.get("LLMLB_NUM_HOSTS", "0") or 0)
    if coordinator and num_hosts > 1:
        host_id = int(os.environ.get("LLMLB_HOST_ID", "0"))
        log.info("jax.distributed: %s host %d/%d",
                 coordinator, host_id, num_hosts)
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_hosts,
            process_id=host_id,
        )
        return True
    if os.environ.get("LLMLB_DISTRIBUTED") == "1":
        log.info("jax.distributed: TPU-pod autodetect")
        jax.distributed.initialize()
        return True
    return False


def build_hybrid_mesh(
    ici: MeshConfig,
    *,
    dcn_dp: int = 1,
    dcn_ep: int = 1,
    devices=None,
) -> Mesh:
    """(dp, sp, ep, tp) mesh whose dp/ep axes may span slices over DCN.

    `ici` sizes the within-slice axes (dp, sp, ep, tp — resolved against the
    per-slice device count); `dcn_dp`/`dcn_ep` multiply dp/ep across slices.
    sp and tp never cross DCN: a per-layer all-reduce (tp) or per-block
    ppermute (sp) over DCN would serialize every step on millisecond RTTs,
    while dp (independent requests) and ep (one a2a per MoE layer, large
    messages) tolerate it.
    """
    devices = list(devices if devices is not None else jax.devices())
    n_slices = dcn_dp * dcn_ep
    per_slice = len(devices) // n_slices
    ici = ici.resolve(per_slice)
    # CPU simulation has no slice topology (devices either lack slice_index
    # or all report the same slice): fall back to process boundaries as the
    # DCN granule.
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    granule = (None in slice_ids) or len(slice_ids) < n_slices
    dev_array = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(ici.dp, ici.sp, ici.ep, ici.tp),
        dcn_mesh_shape=(dcn_dp, 1, dcn_ep, 1),
        devices=devices,
        process_is_granule=granule,
    )
    return Mesh(dev_array, axis_names=("dp", "sp", "ep", "tp"))


# ---------------------------------------------------------------------------
# 2-host CPU self-test (spawned by __graft_entry__.dryrun_multichip)
# ---------------------------------------------------------------------------


def _selftest_worker(process_id: int, num_hosts: int, port: int,
                     devices_per_host: int) -> None:
    """One simulated host: join the cluster, build a hybrid mesh with dp
    across DCN, and run the Mixtral-tiny sharded serving step (BASELINE
    config #5's multi-slice MoE layout at CI size)."""
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=num_hosts,
        process_id=process_id,
    )
    assert jax.device_count() == num_hosts * devices_per_host
    import jax.numpy as jnp

    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.models import mixtral
    from llmlb_tpu.parallel.mesh import default_tp

    cfg = get_preset("debug-moe-tiny")
    # replicas across hosts (DCN), experts + tp inside each host (ICI);
    # gcd keeps ep dividing both the per-host device count and the expert
    # count for any host size
    import math

    per_host = devices_per_host
    ep = math.gcd(per_host, cfg.num_experts)
    tp = default_tp(per_host // ep, cfg.num_heads, cfg.num_kv_heads)
    mesh = build_hybrid_mesh(
        MeshConfig(dp=per_host // (ep * tp), ep=ep, tp=tp),
        dcn_dp=num_hosts,
    )

    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    sh = mixtral.param_shardings(cfg, mesh)
    params = {k: jax.device_put(v, sh[k]) for k, v in params.items()}
    dp_total = mesh.shape["dp"]
    batch = 2 * dp_total
    # the serving programs over an identity block table: row b owns pages
    # 1 + 2b and 2 + 2b of 8 tokens each (page 0 is the trash page)
    ck, cv = mixtral.init_kv_pages(cfg, 2 * batch + 1, 8)
    ck_sh, cv_sh = mixtral.kv_pages_shardings(cfg, mesh)
    ck, cv = jax.device_put(ck, ck_sh), jax.device_put(cv, cv_sh)
    tables = jnp.arange(1, 2 * batch + 1, dtype=jnp.int32).reshape(batch, 2)
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch, 8), 0,
                             cfg.vocab_size)
    lens = jnp.full((batch,), 8, jnp.int32)

    logits, ck, cv = mixtral.prefill_into_pages(
        params, cfg, ids, lens, tables, ck, cv, mesh)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits, ck, cv = mixtral.decode_step_paged(
        params, cfg, tok, lens, ck, cv, tables, mesh)
    # logits span non-addressable devices; reduce to a (replicated) scalar
    # before fetching — each process may only read its local shards
    finite = bool(jax.jit(lambda x: jnp.isfinite(x).all())(logits))
    assert finite, "non-finite logits on hybrid mesh"
    if process_id == 0:
        print(
            f"multihost selftest OK: {num_hosts} hosts x {devices_per_host} "
            f"devices, mesh dp={dp_total} (dcn x ici) ep={mesh.shape['ep']} "
            f"tp={mesh.shape['tp']}, MoE prefill+decode finite",
            flush=True,
        )


def selftest_requests(cfg):
    """The canonical request set for engine lockstep equivalence checks —
    shared by _engine_worker and the single-host baseline in tests so the
    comparison stays structural, not copy-paste."""
    from llmlb_tpu.engine.scheduler import Request, SamplingParams

    rng = np.random.default_rng(11)
    return [
        Request(
            prompt_ids=list(rng.integers(1, cfg.vocab_size, size=(12,))),
            sampling=SamplingParams(temperature=0.0, max_tokens=6),
        )
        for _ in range(2)
    ]


def collect_tokens(reqs, timeout: float = 240.0) -> list[list[int]]:
    from llmlb_tpu.engine.scheduler import event_tokens

    outs = []
    for r in reqs:
        toks = []
        while True:
            kind, val = r.events.get(timeout=timeout)
            if kind == "done":
                break
            if kind == "error":
                raise AssertionError(f"engine error: {val}")
            toks.extend(event_tokens(kind, val))
        outs.append(toks)
    return outs


def _engine_worker(process_id: int, num_hosts: int, port: int,
                   devices_per_host: int) -> None:
    """Lockstep serving across hosts: every process builds the same
    EngineCore over the global device mesh; the leader submits requests and
    the tick-plan broadcast (engine/multihost.py) keeps followers
    dispatching the identical collective programs. Prints the greedy tokens
    so the parent can compare with a single-host run."""
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=num_hosts,
        process_id=process_id,
    )
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import EngineCore

    cfg = get_preset("debug-tiny")
    core = EngineCore(cfg, num_slots=2, slot_capacity=64,
                      prefill_buckets=(16,), seed=0)
    assert (core.coordinator is not None) and (
        core.coordinator.is_leader == (process_id == 0)
    )
    core.start()
    if process_id == 0:
        try:
            reqs = selftest_requests(cfg)
            for r in reqs:
                core.submit(r)
            outs = collect_tokens(reqs)
            print(f"ENGINE_TOKENS {outs!r}", flush=True)
        finally:
            core.stop()  # broadcasts shutdown; followers exit their loops
    else:
        # Follower: the step thread runs the lockstep loop until the leader
        # broadcasts stop — park until then (stopping locally would desync
        # the cluster and strand the leader in its next exchange).
        core._thread.join()
        core.stop()
        print("follower exited cleanly", flush=True)


def run_multihost_selftest(num_hosts: int = 2, devices_per_host: int = 4,
                           timeout_s: float = 300.0,
                           mode: str = "--worker") -> None:
    """Spawn `num_hosts` CPU processes that form a jax.distributed cluster
    and execute a DCN-aware sharded step: mode "--worker" runs the hybrid-
    mesh MoE step, "--engine-worker" runs the full lockstep EngineCore and
    returns the leader's greedy tokens. Raises on any failure."""
    import socket
    import subprocess
    import sys

    def fresh_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    import time as _time

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices_per_host}"
    env.pop("PYTHONSTARTUP", None)

    def spawn_round() -> list:
        port = fresh_port()
        return [
            subprocess.Popen(
                [sys.executable, "-m", "llmlb_tpu.parallel.distributed",
                 mode, str(pid), str(num_hosts), str(port),
                 str(devices_per_host)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
            for pid in range(num_hosts)
        ]

    deadline = _time.monotonic() + timeout_s  # shared: the whole cluster
    # The bind-then-close port probe is racy (another process can claim the
    # port before the coordinator binds it) — retry with a fresh port when
    # the failure is the coordinator bind, not the code under test.
    for attempt in range(3):
        procs = spawn_round()
        outs = []
        for p in procs:
            try:
                out, err = p.communicate(
                    timeout=max(1.0, deadline - _time.monotonic())
                )
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise RuntimeError("multihost selftest timed out")
            outs.append((p.returncode, out, err))
        failures = [(rc, err) for rc, _, err in outs if rc != 0]
        bind_race = any(
            "address already in use" in err.lower()
            or "failed to bind" in err.lower()
            for _, err in failures
        )
        if failures and bind_race and attempt < 2:
            log.warning("coordinator port race; retrying with a fresh port")
            continue
        break
    for rc, out, err in outs:
        if rc != 0:
            raise RuntimeError(
                f"multihost worker failed (rc={rc}):\n{err[-2000:]}"
            )
    if mode == "--engine-worker":
        import ast

        for _, out, _ in outs:
            for line in out.splitlines():
                if line.startswith("ENGINE_TOKENS "):
                    return ast.literal_eval(line[len("ENGINE_TOKENS "):])
        raise RuntimeError(f"no ENGINE_TOKENS line in worker output: {outs}")
    assert any("multihost selftest OK" in out for _, out, _ in outs), outs


if __name__ == "__main__":
    import sys

    if "--worker" in sys.argv or "--engine-worker" in sys.argv:
        mode = "--worker" if "--worker" in sys.argv else "--engine-worker"
        i = sys.argv.index(mode)
        worker = _selftest_worker if mode == "--worker" else _engine_worker
        worker(
            int(sys.argv[i + 1]), int(sys.argv[i + 2]),
            int(sys.argv[i + 3]), int(sys.argv[i + 4]),
        )
    elif "--selftest" in sys.argv:
        run_multihost_selftest()
        print("OK")
