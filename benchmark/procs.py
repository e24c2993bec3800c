"""Child processes of a run: the engine's launcher and the stock gateway.
The handling is `chip_smoke.py`'s (copied, not imported: the yardstick may
not move when the program's files do): every child in its own process group,
output to a log file, and `stop()` terminates and reaps them all on every
exit path — an engine left holding the chip breaks whatever runs next.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys

# libtpu: one process, one chip (set in the child's environment only; on a
# host with four chips an unpinned engine would take all four)
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


def note(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Children:
    def __init__(self, log_dir: str, cwd: str):
        self.log_dir = log_dir
        self.cwd = cwd
        self.procs: list[tuple[str, subprocess.Popen, str]] = []
        os.makedirs(log_dir, exist_ok=True)

    def start(self, name: str, argv: list[str], env: dict) -> subprocess.Popen:
        log_path = os.path.join(self.log_dir, f"{name}.log")
        with open(log_path, "wb") as log:
            proc = subprocess.Popen(
                argv, env=env, cwd=self.cwd, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        self.procs.append((name, proc, log_path))
        return proc

    def stop(self) -> None:
        for _, proc, _ in reversed(self.procs):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name, proc, _ in reversed(self.procs):
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                note(f"{name} ignored SIGTERM; killing")
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            proc.wait()

    def log_tails(self, lines: int = 40) -> None:
        for name, _, log_path in self.procs:
            try:
                with open(log_path, errors="replace") as f:
                    tail = f.read().splitlines()[-lines:]
            except OSError:
                continue
            print(f"---- {name} log (last {len(tail)} lines; whole file: "
                  f"{log_path})", file=sys.stderr)
            print("\n".join(tail), file=sys.stderr, flush=True)
