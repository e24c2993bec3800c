"""Gateway server entry: bootstrap state, serve, graceful shutdown.

Parity with reference server.rs (axum serve + graceful shutdown on signals)
and main.rs/cli (serve/stop/status subcommands; the single-instance lock lives
in lock.py).

Multi-worker serving (``--workers N`` / ``LLMLB_WORKERS``): a supervisor
forks N shared-nothing gateway processes that share the listen port via
SO_REUSEPORT; see gateway/worker.py and docs/deployment.md. The elected
primary (worker 0) runs the health checker, maintenance, the update
manager's background tasks, and the tray.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
import time

from aiohttp import web

from llmlb_tpu.gateway.app import create_app
from llmlb_tpu.gateway.app_state import build_app_state
from llmlb_tpu.gateway.config import ServerConfig, env_bool
from llmlb_tpu.gateway.gate import InferenceGate  # noqa: F401  (re-export)
from llmlb_tpu.gateway.lock import ServerLock
from llmlb_tpu.gateway.update import UpdateManager
from llmlb_tpu.gateway.worker import (
    WorkerInfo,
    current_worker,
    run_supervisor,
    supports_reuse_port,
    worker_count_from_env,
)

log = logging.getLogger("llmlb_tpu.gateway.server")


def maybe_install_uvloop() -> bool:
    """Opt-in uvloop (LLMLB_UVLOOP=1): a drop-in libuv event loop worth
    ~2-3x on the pure proxy path. Graceful fallback — uvloop is not a
    dependency of this repo, so absence logs and keeps the stdlib loop."""
    if not env_bool("LLMLB_UVLOOP", False):
        return False
    try:
        import uvloop  # type: ignore[import-not-found]
    except ImportError:
        log.warning("LLMLB_UVLOOP=1 but uvloop is not installed; "
                    "using the stdlib asyncio event loop")
        return False
    asyncio.set_event_loop_policy(uvloop.EventLoopPolicy())
    log.info("uvloop event loop policy installed")
    return True


async def run_server(config: ServerConfig | None = None, *,
                     worker: WorkerInfo | None = None,
                     acquire_lock: bool = True) -> None:
    config = config or ServerConfig.from_env()
    worker = worker or current_worker()
    os.makedirs(os.path.dirname(config.database_url) or ".", exist_ok=True)

    from llmlb_tpu.native import ensure_native_built

    ensure_native_built()  # blocking make belongs here, not in a request path
    # tiktoken fetches its vocabulary over the network on first use: resolve
    # it once, on a thread, so no request ever waits on (or retries) it
    from llmlb_tpu.gateway.token_accounting import load_encoder

    encoder_load = asyncio.create_task(asyncio.to_thread(load_encoder))

    # In multi-worker mode the supervisor holds the instance lock for the
    # whole group; forked workers must not fight over it.
    lock = ServerLock.acquire(config.port) if acquire_lock else None
    state = await build_app_state(config, worker=worker)
    stop_event = asyncio.Event()

    from llmlb_tpu import __version__

    # Real self-update wiring when LLMLB_UPDATE_REPO/ARTIFACT are set:
    # restart = graceful exit, the supervisor re-execs the (new) artifact.
    state.update_manager = UpdateManager.from_env(
        state.gate, state.http, __version__, events=state.events,
        drain_timeout_s=config.update_drain_timeout_s,
        restart_cb=stop_event.set,
    )
    # Background update checks run on the elected primary only; an apply
    # landing on any worker still drains and exits that worker, which takes
    # the whole group down for the external supervisor to re-exec
    # (docs/deployment.md).
    if worker.is_primary:
        state.update_manager.start_background_tasks()
    app = create_app(state)

    # Short shutdown grace: idle keep-alive connections must not delay a
    # supervisor restart (observed: default 60 s stalls the update re-exec).
    # Access logging is OFF on the proxy hot path by default: one formatted
    # log line per request costs more than the rest of the accounting
    # combined at high request rates (LLMLB_ACCESS_LOG=1 re-enables).
    access_log = (logging.getLogger("aiohttp.access")
                  if env_bool("LLMLB_ACCESS_LOG", False) else None)
    runner = web.AppRunner(app, shutdown_timeout=5.0, access_log=access_log)
    await runner.setup()
    site = web.TCPSite(
        runner, config.host, config.port,
        # N workers bind the same (host, port); the kernel load-balances
        # accepted connections across their accept queues.
        reuse_port=True if worker.multi else None,
    )
    await site.start()
    log.info("llmlb_tpu gateway listening on %s:%d (worker %d/%d)",
             config.host, config.port, worker.index, worker.count)

    probe_host = config.host
    if probe_host in ("0.0.0.0", "::", ""):
        probe_host = "127.0.0.1"
    elif ":" in probe_host:  # bare IPv6 address needs brackets in a URL
        probe_host = f"[{probe_host}]"

    # Tray equivalent (reference gui/tray.rs, win/mac only): opt-in on these
    # headless TPU hosts; menu/notifications surface at /api/system/tray.
    # One tray per gateway instance, not per worker.
    if worker.is_primary and os.environ.get(
        "LLMLB_TRAY", "0"
    ).lower() in ("1", "true"):
        from llmlb_tpu.gateway.tray import TrayController

        state.tray = TrayController(
            f"http://{probe_host}:{config.port}/dashboard",
            state.update_manager,
            events=state.events,
            quit_cb=stop_event.set,
        )
        await state.tray.start()

    async def self_health() -> bool:
        try:
            async with state.http.get(
                f"http://{probe_host}:{config.port}/health", timeout=2
            ) as r:
                return r.status == 200
        except Exception:
            return False

    # If we just restarted into a freshly applied update, watch health for
    # 30 s and roll back from .bak on failure (reference post-restart watch).
    # Primary-only: one watcher per instance decides the rollback.
    watch_task = (
        asyncio.create_task(
            state.update_manager.post_restart_watch(self_health)
        )
        if worker.is_primary else None
    )

    hard_stop = asyncio.Event()
    first_signal_at = 0.0

    def on_signal() -> None:
        nonlocal first_signal_at
        now = time.monotonic()
        if stop_event.is_set():
            # Second signal escalates to hard stop — but only when it is a
            # deliberate repeat, not a duplicate delivery of the first:
            # with --workers, a terminal Ctrl-C reaches each child via the
            # process group AND via the supervisor's forward (same for
            # systemd KillMode=control-group), microseconds apart. That
            # pair must drain gracefully, not abort in-flight streams.
            if now - first_signal_at > 0.5:
                hard_stop.set()
        else:
            first_signal_at = now
        stop_event.set()

    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, on_signal)
        except NotImplementedError:
            pass
    try:
        await stop_event.wait()
    finally:
        log.info("shutting down")
        encoder_load.cancel()
        if watch_task is not None:
            watch_task.cancel()
        if state.tray is not None:
            await state.tray.stop()
        await state.update_manager.stop_background_tasks()
        # Drain in-flight inference before tearing the server down: with the
        # 5 s shutdown grace above, an ordinary SIGTERM would otherwise cut
        # long-running generations mid-stream. Skipped after a FORCE apply
        # (its point is aborting wedged streams) and cut short by a second
        # signal. A NORMAL update apply has already drained, so the wait
        # returns immediately there.
        from llmlb_tpu.gateway.update import ApplyMode

        forced = getattr(
            state.update_manager, "last_apply_mode", None
        ) == ApplyMode.FORCE
        state.gate.start_rejecting()
        if not forced and not hard_stop.is_set():
            drain = asyncio.ensure_future(state.gate.wait_for_idle(30.0))
            bail = asyncio.ensure_future(hard_stop.wait())
            done, pending = await asyncio.wait(
                {drain, bail}, return_when=asyncio.FIRST_COMPLETED
            )
            for p in pending:
                p.cancel()
            if drain in done and not drain.result():
                log.warning("shutdown drain timeout with %d in flight",
                            state.gate.in_flight)
        await runner.cleanup()
        if lock is not None:
            lock.release()


def serve_multi_worker(config: ServerConfig, workers: int) -> None:
    """Supervisor path: hold the instance lock, build the native library
    once (N children racing `make` would step on each other), fork the
    workers, and wait. Each child re-inits logging with its worker id (the
    file sink stays primary-only — N TimedRotatingFileHandlers would race
    the midnight rotation) and runs the ordinary run_server."""
    from llmlb_tpu.gateway.logging_setup import init_logging
    from llmlb_tpu.native import ensure_native_built

    os.makedirs(os.path.dirname(config.database_url) or ".", exist_ok=True)
    ensure_native_built()
    lock = ServerLock.acquire(config.port)
    try:
        def child_main(worker: WorkerInfo) -> int:
            init_logging(file_sink=worker.is_primary)
            maybe_install_uvloop()
            asyncio.run(
                run_server(config, worker=worker, acquire_lock=False)
            )
            return 0

        code = run_supervisor(workers, child_main)
    finally:
        lock.release()
    if code:
        raise SystemExit(code)


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(prog="llmlb", description="TPU-native LLM gateway")
    sub = parser.add_subparsers(dest="command")

    serve = sub.add_parser("serve", help="run the gateway")
    serve.add_argument("--host", default=None)
    serve.add_argument("--port", type=int, default=None)
    serve.add_argument(
        "--workers", type=int, default=None,
        help="number of gateway worker processes sharing the port via "
             "SO_REUSEPORT (default LLMLB_WORKERS or 1)",
    )

    sub.add_parser("status", help="check whether a gateway is running")
    stop = sub.add_parser("stop", help="stop a running gateway")
    stop.add_argument("--port", type=int, default=None)
    assistant = sub.add_parser(
        "assistant", help="API helper: sanitized curl, openapi, guides"
    )
    assistant.add_argument("assistant_args", nargs=argparse.REMAINDER)

    args = parser.parse_args(argv)
    if args.command == "assistant":
        from llmlb_tpu.gateway.assistant import main as assistant_main

        raise SystemExit(assistant_main(args.assistant_args))
    from llmlb_tpu.gateway.logging_setup import init_logging

    # stderr + daily-rotated file sink (reference logging.rs:41-182)
    init_logging()

    config = ServerConfig.from_env()
    if getattr(args, "host", None):
        config = config.__class__(**{**config.__dict__, "host": args.host})
    if getattr(args, "port", None):
        config = config.__class__(**{**config.__dict__, "port": args.port})

    if args.command in (None, "serve"):
        workers = worker_count_from_env(getattr(args, "workers", None))
        if workers > 1 and not supports_reuse_port():
            log.warning("--workers %d requested but SO_REUSEPORT is "
                        "unavailable on this platform; serving "
                        "single-process", workers)
            workers = 1
        if workers > 1:
            serve_multi_worker(config, workers)
        else:
            # Pin the 1-of-1 identity explicitly (and in the env, which
            # current_worker()/logging read): a lingering LLMLB_WORKERS=4
            # must not make this lone process bind with reuse_port or wait
            # for gossip siblings that will never exist.
            os.environ["LLMLB_WORKERS"] = "1"
            maybe_install_uvloop()
            asyncio.run(run_server(config, worker=WorkerInfo(0, 1)))
    elif args.command == "status":
        info = ServerLock.status(config.port)
        if info:
            print(f"running: pid={info['pid']} port={info['port']}")
        else:
            print("not running")
    elif args.command == "stop":
        if ServerLock.stop(config.port):
            print("stopped")
        else:
            print("not running")


if __name__ == "__main__":
    main()
