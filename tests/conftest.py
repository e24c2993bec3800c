"""Test configuration: force an 8-device virtual CPU platform for sharding tests.

Mirrors the reference's "multi-node without a cluster" strategy (SURVEY.md §4):
everything runs in-process — JAX on a virtual 8-device CPU mesh, gateway servers on
ephemeral localhost ports, SQLite in-memory/tmpdir.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# fp32 tests compare against float64/torch references; JAX's default ("fastest")
# matmul precision is bf16-grade even on CPU.
jax.config.update("jax_default_matmul_precision", "highest")

import asyncio  # noqa: E402
import gc  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

# Half of `vm.max_map_count`'s default (65,530): see `_room_for_compiled_programs`.
MEMORY_MAPS_BUDGET = 32_000


@pytest.fixture(autouse=True)
def _room_for_compiled_programs():
    """A worker keeps every program its tests compiled, and a loaded CPU
    executable is a few memory maps. Near the kernel's limit
    (`vm.max_map_count`) the next compile's `mmap` fails and the worker dies
    of a segmentation fault inside XLA: at PR 47 an xdist worker of the
    tier-1 run held 63,907 maps twenty seconds before
    `tests/engine/test_window_family.py` fell in it, three runs of three,
    and the file passes alone. Past half the limit the compiled programs are
    dropped; a test that needs one compiles it again."""
    try:
        with open("/proc/self/maps", "rb") as f:
            maps = sum(1 for _ in f)
    except OSError:  # no /proc, no such count to watch
        maps = 0
    if maps > MEMORY_MAPS_BUDGET:
        jax.clear_caches()
        gc.collect()


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (pytest-asyncio is not available)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


def pytest_configure(config):
    """Build the native library once up front so tests exercise native
    paths (router core, SSE scanner, HRW owner, ct_equal). When no C++
    toolchain is present the parity tests skip with a VISIBLE reason
    (native_skip_reason below feeds their skipif) — never silently."""
    import shutil
    import sys

    compiler = shutil.which("g++") or shutil.which("c++") or shutil.which("cc")
    built = False
    try:
        from llmlb_tpu.native import ensure_native_built

        built = ensure_native_built()
    except Exception as e:
        sys.stderr.write(f"[conftest] native build errored: {e}\n")
    if not built:
        sys.stderr.write(
            "[conftest] native library unavailable "
            f"(compiler={'none found' if not compiler else compiler}); "
            "native-parity tests will SKIP with that reason\n"
        )


def native_skip_reason() -> str | None:
    """None when the native library is loadable; otherwise the reason the
    parity tests print in their skip line (tier-1 must show WHY)."""
    import shutil

    try:
        from llmlb_tpu.native import load_native

        if load_native() is not None:
            return None
    except Exception as e:
        return f"native library failed to load: {e}"
    compiler = shutil.which("g++") or shutil.which("c++") or shutil.which("cc")
    if compiler is None:
        return ("no C++ toolchain on this host (install g++ or run "
                "`make -C native` elsewhere)")
    return "native library not built (run `make -C native`)"


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {len(devices)}"
    return devices
