"""Bring-up rules: no backend nobody asked for, a compile cache that can be
placed from outside, no stale native library, and a chip smoke that fails
fast where there is no chip.

The entry points are driven as a user would, in child processes: each
decides its backend from its own environment, which a test in this process
(pinned to the CPU by conftest.py) cannot show.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import time
import urllib.request

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _env(**overrides) -> dict:
    """This process's environment with the repo importable; a value of None
    removes the variable."""
    env = {**os.environ, "PYTHONPATH": REPO}
    for k, v in overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ------------------------------------------------------------- compile cache


def test_compile_cache_dir_from_the_environment_is_left_to_jax(monkeypatch):
    from llmlb_tpu import startup

    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    assert startup.configure_compile_cache() == "/x"
    assert updates == []  # JAX reads the variable itself; we set nothing


def test_compile_cache_defaults_to_the_checkout_from_any_directory(tmp_path):
    code = ("from llmlb_tpu.startup import configure_compile_cache as c\n"
            "import jax\n"
            "print(c()); print(jax.config.jax_compilation_cache_dir)")
    seen = set()
    for cwd in (tmp_path, REPO):
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, text=True, check=True,
            capture_output=True, timeout=120,
            env=_env(JAX_COMPILATION_CACHE_DIR=None, JAX_PLATFORMS="cpu"),
        ).stdout.split()
        assert out[0] == out[1]  # what we return is what JAX was given
        seen.add(out[0])
    assert seen == {os.path.join(REPO, ".jax_cache")}


# ------------------------------------------------------------ engine backend


def test_engine_server_refuses_a_cpu_nobody_asked_for():
    """With no accelerator JAX falls back to the CPU on its own; the server
    must exit instead of serving from it."""
    proc = subprocess.run(
        [sys.executable, "-m", "llmlb_tpu.engine.server",
         "--preset", "debug-tiny", "--port", str(_free_port())],
        env=_env(JAX_PLATFORMS=None), capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no accelerator found" in proc.stderr


def test_engine_server_serves_from_the_cpu_when_asked():
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "llmlb_tpu.engine.server",
         "--preset", "debug-tiny", "--port", str(port)],
        env=_env(JAX_PLATFORMS="cpu"), stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    try:
        health = None
        deadline = time.monotonic() + 120
        while health is None and time.monotonic() < deadline:
            assert proc.poll() is None, "engine server exited"
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/api/health", timeout=2) as r:
                    health = json.loads(r.read())
            except OSError:
                time.sleep(0.2)
        assert health is not None, "engine server never became ready"
        assert health["tpu"]["accelerator"] == "cpu"
        assert health["attention"]["mode"] == "xla"
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    assert proc.poll() is not None


def test_attention_dispatch_records_what_it_resolved():
    import jax.numpy as jnp

    from llmlb_tpu.ops import attention

    q = jnp.zeros((1, 1, 4, 8), jnp.float32)
    pages = jnp.zeros((2, 3, 4, 2, 8), jnp.float32)  # [L, P, PS, K, D]
    attention.paged_attention_decode(
        q, pages, pages, 1, jnp.zeros((1, 2), jnp.int32),
        jnp.ones((1,), jnp.int32))
    assert attention.attention_mode() == "xla"  # CPU backend
    assert attention.traced_routes()["paged_decode"] == "xla"


# ---------------------------------------------------------------- chip smoke


def test_chip_smoke_fails_fast_without_a_chip_and_never_imports_jax():
    code = ("import sys, chip_smoke\n"
            "rc = chip_smoke.main()\n"
            "print('PARENT_IMPORTED_JAX', 'jax' in sys.modules,"
            " file=sys.stderr)\n"
            "sys.exit(rc)")
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line
    assert "PARENT_IMPORTED_JAX False" in proc.stderr
    assert time.monotonic() - start < 120
    # nothing it started is left behind
    left = subprocess.run(["pgrep", "-f", "chip_smoke.py --kernel-leg"],
                          capture_output=True, text=True).stdout
    assert left.strip() == ""


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env=_env(PYTHONPATH=None), capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_chip_smoke_result_line_has_the_contract_keys_and_no_others():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.contract_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "extra": 0})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


# -------------------------------------------------------------------- native


def test_failed_native_build_never_loads_a_stale_library(monkeypatch):
    import llmlb_tpu.native as native

    def failing_make(*args, **kwargs):
        raise subprocess.CalledProcessError(2, "make")

    monkeypatch.setattr(native, "_build_ok", None)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native.subprocess, "run", failing_make)
    assert native.ensure_native_built() is False
    # whatever libllmlb_native.so is on disk, this process uses Python paths
    assert native.load_native() is None


# ---------------------------------------------------------- token estimation


def test_unavailable_tiktoken_encoder_is_resolved_once(monkeypatch):
    import tiktoken

    from llmlb_tpu.gateway import token_accounting as ta

    calls = []

    def no_network(name):
        calls.append(name)
        raise ConnectionError("no network")

    monkeypatch.setattr(tiktoken, "get_encoding", no_network)
    monkeypatch.setattr(ta, "_encoding", None)
    assert ta.load_encoder() is False
    assert ta.estimate_tokens("x" * 40) == 10  # chars / 4
    assert ta.estimate_tokens("y" * 8) == 2
    assert calls == ["cl100k_base"]  # requests never retry the fetch
