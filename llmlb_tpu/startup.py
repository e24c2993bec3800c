"""What every entry point that touches JAX does first: place the compilation
cache, then resolve the backend.

Called from the engine server, benchmark/launcher.py, chip_smoke.py's
kernel child and __graft_entry__.py — and from no constructor, so importing
or testing the library configures nothing.
"""

from __future__ import annotations

import faulthandler
import logging
import os

log = logging.getLogger("llmlb_tpu.startup")

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX at its persistent compilation cache; returns the directory.

    The directory is part of the cache key, so it must not move between
    runs: `JAX_COMPILATION_CACHE_DIR` when the caller set it (JAX reads that
    variable itself, and nothing is set in code then), otherwise
    `<checkout>/.jax_cache`, found from this file's location — a copy of the
    tree that is not a git repository resolves to the same place."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def resolve_backend(init_timeout: float | None = None) -> list:
    """The process's first device touch: bounded, and never a CPU nobody
    asked for. Returns `jax.devices()`.

    A backend init that hangs dumps every thread's stack and exits the
    process after `init_timeout` seconds (LLMLB_INIT_TIMEOUT, default 600;
    0 = unbounded) — the watchdog lives in this process, so no second
    process ever claims the chip. When libtpu finds no chip JAX falls back
    to the CPU with a warning; running a device workload there would report
    CPU speed under the device's name, so that is an error unless the
    caller's first choice in JAX_PLATFORMS is the cpu."""
    if init_timeout is None:
        raw = os.environ.get("LLMLB_INIT_TIMEOUT", "")
        try:
            init_timeout = float(raw) if raw else 600.0
        except ValueError:
            log.warning("LLMLB_INIT_TIMEOUT=%r is not a number; using 600",
                        raw)
            init_timeout = 600.0
    import jax

    if init_timeout > 0:
        faulthandler.dump_traceback_later(init_timeout, exit=True)
    try:
        devices = jax.devices()
    finally:
        faulthandler.cancel_dump_traceback_later()
    first_choice = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    if devices[0].platform == "cpu" and first_choice.strip().lower() != "cpu":
        raise SystemExit(
            "no accelerator found: JAX fell back to the CPU. Set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose."
        )
    return devices
