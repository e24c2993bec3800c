"""Prove the serving path starts and answers on the chip.

    python chip_smoke.py

Drives the system the way a user does — a client, the gateway
(`python -m llmlb_tpu.gateway.server serve`) and a registered `tpu://`
engine (`python -m llmlb_tpu.engine.server --preset tinyllama-1.1b`) — at
the full width and depth of TinyLlama-1.1B with random weights from the
preset's seed, on ONE chip. Only if every phase passed does it exit 0 and
print, as the last (and only) line of stdout, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. Everything else it learned — versions,
mesh, attention path, per-kernel outcome, token counts, times, compile-cache
entries — is one JSON line on stderr and `chiprun_out/chip_smoke/report.json`.
Anything else, including "no chip found", is a non-zero exit with nothing on
stdout, the reason on stderr and the children's logs under
`chiprun_out/chip_smoke/`.

A chip belongs to one process at a time, so this parent never imports jax.
Every process that needs the chip is a child, and they run one after
another:

  child A  kernel leg (this file, `--kernel-leg`): every Pallas kernel the
           `auto` dispatch can route to on one chip, compiled by Mosaic
           (`interpret=False`) at TinyLlama's shapes and compared with the
           XLA path; exits before the engine starts
  child B  the engine server, normal CLI, default slots and buckets
  child C  the gateway (no jax), fresh LLMLB_DATA_DIR

A and B see exactly one chip through libtpu's visibility variables
(ONE_CHIP_ENV below), set in the child's environment only — on a host with
four chips an unpinned engine would take all four, run tp=4 and never
exercise the Pallas path.
"""

from __future__ import annotations

import functools
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
MODEL = "tinyllama-1.1b"
DEADLINE_S = 1100  # the contract allows 1200, compilation included
WEIGHT_BYTES = 2 * 1_100_000_000  # 1.1e9 bf16 parameters, 2.05 GiB
STREAMS = 8  # concurrent chats in (c): one per default engine slot

# libtpu 0.0.34: one process, one chip.
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


class SmokeFailure(Exception):
    pass


T0 = time.monotonic()


def note(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------------ children


class Children:
    """Every process the smoke starts; stop() terminates and reaps them all
    on every exit path — an engine left holding the chip breaks whatever
    the caller runs next."""

    def __init__(self) -> None:
        self.procs: list[tuple[str, subprocess.Popen, str]] = []

    def start(self, name: str, argv: list[str], env: dict) -> subprocess.Popen:
        log_path = os.path.join(OUT_DIR, f"{name}.log")
        with open(log_path, "wb") as log:
            proc = subprocess.Popen(
                argv, env=env, cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,  # its own group: killpg reaps forks
            )
        self.procs.append((name, proc, log_path))
        return proc

    def stop(self) -> None:
        for _, proc, _ in reversed(self.procs):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for name, proc, _ in reversed(self.procs):
            try:
                proc.wait(timeout=45)  # engine drain grace is 30 s
            except subprocess.TimeoutExpired:
                note(f"{name} ignored SIGTERM; killing")
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
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


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------- http


def http_json(method: str, url: str, body: dict | None = None,
              headers: dict | None = None, timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode() or "null")
    except urllib.error.HTTPError as e:
        return e.code, {"error_body": e.read().decode(errors="replace")[:500]}


def wait_http(url: str, proc: subprocess.Popen, what: str,
              timeout_s: float) -> float:
    """Poll until `url` answers 200; returns seconds waited. Fails at once
    if the process exits."""
    start = time.monotonic()
    while time.monotonic() - start < timeout_s:
        if proc.poll() is not None:
            raise SmokeFailure(f"{what} exited with code {proc.returncode} "
                               "before it was ready")
        try:
            with urllib.request.urlopen(url, timeout=2) as r:
                if r.status == 200:
                    return time.monotonic() - start
        except OSError:
            pass
        time.sleep(0.25)
    raise SmokeFailure(f"{what} not ready after {timeout_s:.0f}s")


def stream_sse(url: str, body: dict, headers: dict,
               timeout: float = 600.0) -> dict:
    """POST and read a server-sent-event stream to its end. Returns the
    parsed `data:` payloads, the `event:` names, and when the first frame
    carrying text arrived (most tokens of a random-weight model decode to
    no text, so that can be late or never; the engine's own time to first
    token is read from its timeline)."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json", **headers})
    start = time.monotonic()
    frames: list[dict] = []
    events: list[str] = []
    first_text_s = None
    done = False
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            status = r.status
            for raw in r:
                line = raw.decode(errors="replace").strip()
                if line.startswith("event:"):
                    events.append(line[6:].strip())
                elif line.startswith("data:"):
                    data = line[5:].strip()
                    if data == "[DONE]":
                        done = True
                        continue
                    payload = json.loads(data)
                    frames.append(payload)
                    if first_text_s is None and _frame_text(payload):
                        first_text_s = time.monotonic() - start
    except urllib.error.HTTPError as e:
        return {"status": e.code, "frames": [], "events": [],
                "error": e.read().decode(errors="replace")[:500]}
    return {"status": status, "frames": frames, "events": events,
            "done": done, "first_text_s": first_text_s,
            "total_s": time.monotonic() - start}


def _frame_text(payload: dict) -> str:
    if payload.get("type") == "response.output_text.delta":
        return payload.get("delta") or ""
    for choice in payload.get("choices") or []:
        text = (choice.get("delta") or {}).get("content")
        if text:
            return text
    return ""


# ------------------------------------------------------------------- traffic


def prompt_text(seed: int, chars: int) -> str:
    """Seeded filler of about `chars` characters (ByteTokenizer: about one
    token per character). Distinct from the first word on, so no two
    prompts share a cacheable prefix by accident."""
    rng = random.Random(seed)
    words = ["chip", "page", "slot", "burst", "token", "gate", "mesh",
             "cache", "head", "layer", "queue", "batch", "probe", "shard"]
    out = f"request {seed}:"
    while len(out) < chars:
        out += " " + rng.choice(words)
    return out[:chars]


class Traffic:
    """The four request kinds of the smoke, sent to `base` (the gateway in
    the smoke; an engine directly in the one-off four-chip run). Every
    request carries an X-Request-Id so the engine's flight recorder can be
    asked what happened to it."""

    def __init__(self, base: str, engine_base: str, headers: dict,
                 *, model: str = MODEL, chat_chars: int = 300,
                 chat_tokens: int = 300, long_chars: int = 1500):
        self.base = base
        self.engine_base = engine_base
        self.headers = headers
        self.model = model
        self.chat_chars = chat_chars
        self.chat_tokens = chat_tokens
        self.long_chars = long_chars
        self.requests: list[dict] = []  # one record per request sent
        self._lock = threading.Lock()

    # -- one request of each shape

    def chat(self, rid: str, prompt: str, max_tokens: int) -> dict:
        start = time.monotonic()
        status, body = http_json(
            "POST", f"{self.base}/v1/chat/completions",
            {"model": self.model, "temperature": 0, "max_tokens": max_tokens,
             "messages": [{"role": "user", "content": prompt}]},
            headers={**self.headers, "X-Request-Id": rid}, timeout=600)
        rec = {"id": rid, "status": status,
               "total_s": round(time.monotonic() - start, 3)}
        if status == 200:
            choice = body["choices"][0]
            rec.update(text=choice["message"]["content"],
                       finish_reason=choice.get("finish_reason"),
                       completion_tokens=body["usage"]["completion_tokens"],
                       prompt_tokens=body["usage"]["prompt_tokens"])
        else:
            rec["error"] = body
        return self._record(rec)

    def chat_stream(self, rid: str, prompt: str, max_tokens: int) -> dict:
        out = stream_sse(
            f"{self.base}/v1/chat/completions",
            {"model": self.model, "temperature": 0, "max_tokens": max_tokens,
             "stream": True, "stream_options": {"include_usage": True},
             "messages": [{"role": "user", "content": prompt}]},
            {**self.headers, "X-Request-Id": rid})
        rec = {"id": rid, "status": out["status"],
               "events": out["events"], "error": out.get("error")}
        finish, usage = None, {}
        for f in out["frames"]:
            if "error" in f:
                rec["error"] = f["error"]
            for choice in f.get("choices") or []:
                finish = choice.get("finish_reason") or finish
            usage = f.get("usage") or usage
        rec.update(finish_reason=finish, done=out.get("done"),
                   completion_tokens=usage.get("completion_tokens", 0),
                   prompt_tokens=usage.get("prompt_tokens", 0),
                   first_text_s=out.get("first_text_s"),
                   total_s=round(out.get("total_s", 0.0), 3))
        return self._record(rec)

    def engine_token_ids(self, rid: str, prompt: str,
                         max_tokens: int) -> list[int]:
        """The same chat asked of the ENGINE directly, armed the way the
        gateway arms a durable stream, for the token ids it commits (the
        gateway strips those frames). A random-weight model's tokens decode
        to almost no text, so the ids are what make "the same answer twice"
        mean something."""
        out = stream_sse(
            f"{self.engine_base}/v1/chat/completions",
            {"model": self.model, "temperature": 0, "max_tokens": max_tokens,
             "stream": True, "llmlb_replay": True,
             "messages": [{"role": "user", "content": prompt}]},
            {"X-Request-Id": rid})
        ids = [t for f in out["frames"] if f.get("object") == "llmlb.replay"
               for t in f["tokens"]]
        self._record({"id": rid, "status": out["status"],
                      "events": out["events"], "error": out.get("error"),
                      "done": out.get("done"), "completion_tokens": len(ids),
                      "finish_reason": "direct", "token_ids": ids,
                      "total_s": round(out.get("total_s", 0.0), 3)})
        return ids

    def responses_stream(self, rid: str, prompt: str, max_tokens: int) -> dict:
        out = stream_sse(
            f"{self.base}/v1/responses",
            {"model": self.model, "temperature": 0, "stream": True,
             "max_output_tokens": max_tokens, "input": prompt},
            {**self.headers, "X-Request-Id": rid})
        rec = {"id": rid, "status": out["status"], "events": out["events"],
               "error": out.get("error")}
        final = next((f for f in reversed(out["frames"])
                      if f.get("type") in ("response.completed",
                                           "response.incomplete")), None)
        usage = ((final or {}).get("response") or {}).get("usage") or {}
        rec.update(
            finish_reason=(final or {}).get("type"),
            done=final is not None,
            completion_tokens=usage.get("output_tokens", 0),
            prompt_tokens=usage.get("input_tokens", 0),
            first_text_s=out.get("first_text_s"),
            total_s=round(out.get("total_s", 0.0), 3))
        return self._record(rec)

    def _record(self, rec: dict) -> dict:
        with self._lock:
            self.requests.append(rec)
        return rec

    # -- the run

    def engine_tokens(self) -> int:
        _, health = http_json("GET", f"{self.engine_base}/api/health")
        return int(health["engine"]["total_tokens"])

    def run(self) -> dict:
        """(a)-(d) in order; returns what the checks need beyond the
        per-request records."""
        facts: dict = {}
        # (a) the same short greedy chat twice: the answers must be
        # identical — as text through the gateway, as token ids from the
        # engine
        hello = "Say hello to the chip."
        a1 = self.chat("smoke-a1", hello, 32)
        a2 = self.chat("smoke-a2", hello, 32)
        ids1 = self.engine_token_ids("smoke-a3", hello, 32)
        ids2 = self.engine_token_ids("smoke-a4", hello, 32)
        facts["greedy_repeat_identical"] = (
            a1["status"] == a2["status"] == 200
            and (a1["text"], a1["completion_tokens"], a1["finish_reason"])
            == (a2["text"], a2["completion_tokens"], a2["finish_reason"])
            and ids1 == ids2 and len(ids1) == a1["completion_tokens"])
        facts["greedy_text"] = a1.get("text")
        facts["greedy_token_ids"] = ids1
        note(f"(a) greedy x2: identical={facts['greedy_repeat_identical']} "
             f"tokens={a1.get('completion_tokens')} ids={ids1[:6]}...")
        # (b) one streamed /v1/responses, parsed to its terminal event
        b = self.responses_stream("smoke-b", "Count the pages in a slot.", 64)
        note(f"(b) responses stream: {b['status']} "
             f"tokens={b['completion_tokens']} end={b['finish_reason']}")
        # (c) concurrent streamed chats, long enough that the batch decodes
        # in bursts and its context window grows past 256 and 512. Half
        # start first and the rest once those are well under way, so that
        # slots free up while streams are still decoding: (d) below is
        # then chunk-prefilled BETWEEN decode steps, not after them.
        threads: list[threading.Thread] = []

        def launch(kind, rid, *args):
            t = threading.Thread(target=kind, args=(rid, *args), daemon=True)
            t.start()
            threads.append(t)

        first_half = STREAMS // 2
        base_tokens = self.engine_tokens()
        for i in range(first_half):
            launch(self.chat_stream, f"smoke-c{i}",
                   prompt_text(100 + i, self.chat_chars), self.chat_tokens)
        lead = first_half * self.chat_tokens // 3
        deadline = time.monotonic() + 600
        while (self.engine_tokens() - base_tokens < lead
               and time.monotonic() < deadline
               and any(t.is_alive() for t in threads)):
            time.sleep(0.25)
        for i in range(first_half, STREAMS):
            launch(self.chat_stream, f"smoke-c{i}",
                   prompt_text(100 + i, self.chat_chars), self.chat_tokens)
        # (d) a prompt beyond the largest one-shot prefill bucket (512):
        # chunked prefill through the paged extend kernel, while (c) runs
        long_prompt = prompt_text(7, self.long_chars)
        self.chat("smoke-d1", long_prompt, 16)
        facts["c_alive_when_d_answered"] = sum(t.is_alive() for t in threads)
        # (d) again, at once — the prefix cache keeps a few donors, LRU, and
        # every finishing (c) stream donates: the prompt's full pages are
        # shared from the cache instead of prefilled
        _, before = http_json("GET", f"{self.engine_base}/api/health")
        self.chat("smoke-d2", long_prompt, 16)
        _, after = http_json("GET", f"{self.engine_base}/api/health")
        facts["prefix_hits_from_repeat"] = (
            after["metrics"]["prefix_hits_total"]
            - before["metrics"]["prefix_hits_total"])
        note(f"(d) long prompt x2: answered with "
             f"{facts['c_alive_when_d_answered']} streams still decoding; "
             f"prefix hits +{facts['prefix_hits_from_repeat']}")
        for t in threads:
            t.join(timeout=600)
        if any(t.is_alive() for t in threads):
            raise SmokeFailure("a concurrent stream never finished")
        note(f"(c) {STREAMS} concurrent streams done")
        # the engine's own account of each request (flight recorder)
        for rec in self.requests:
            _, tl = http_json(
                "GET", f"{self.engine_base}/api/requests/{rec['id']}/timeline")
            events = (tl or {}).get("events") or []
            chunks = [e.get("attrs") or {} for e in events
                      if e.get("event") == "prefill_chunk"]
            rec["prefill_chunks"] = len(chunks)
            rec["cached_tokens"] = max(
                (c.get("cached_tokens", 0) for c in chunks), default=0)
            rec["engine_ttft_s"] = next(
                ((e.get("attrs") or {}).get("ttft_s") for e in events
                 if e.get("event") == "finished"), None)
        return facts

    def failures(self) -> list[str]:
        bad = []
        for r in self.requests:
            if r["status"] != 200:
                bad.append(f"{r['id']}: HTTP {r['status']} {r.get('error')}")
            elif r.get("error") or "error" in (r.get("events") or []):
                bad.append(f"{r['id']}: error frame {r.get('error')}")
            elif r.get("completion_tokens", 0) < 1:
                bad.append(f"{r['id']}: no token")
            elif not r.get("finish_reason"):
                bad.append(f"{r['id']}: no finish_reason")
            elif r.get("done") is False:
                bad.append(f"{r['id']}: stream did not reach its end")
        return bad


# ---------------------------------------------------------------- the checks


def check_engine(health: dict, log_text: str, traffic: Traffic,
                 facts: dict, *, platform: str = "tpu",
                 weight_bytes: int = WEIGHT_BYTES) -> list[str]:
    """Everything that must hold after the traffic; returns the failures."""
    bad = traffic.failures()
    tpu = health["tpu"]
    if tpu["accelerator"] != platform:
        bad.append(f"engine accelerator is {tpu['accelerator']!r}")
    if tpu["chip_count"] != 1:
        bad.append(f"engine sees {tpu['chip_count']} chips, want 1")
    perf = health.get("perf") or {}
    if platform == "tpu":
        if not perf.get("chip"):
            bad.append(f"device kind {perf.get('device_kind')!r} is not in "
                       "telemetry.CHIP_SPECS")
        if not tpu["hbm_total_bytes"] > 0:
            bad.append("hbm_total_bytes is 0")
        if tpu["hbm_used_bytes"] < weight_bytes:
            bad.append(f"hbm_used_bytes {tpu['hbm_used_bytes']} is less "
                       f"than the weights ({weight_bytes})")
    if health["metrics"]["errors_total"] != 0:
        bad.append(f"engine errors_total={health['metrics']['errors_total']}")
    if not facts.get("greedy_repeat_identical"):
        bad.append("greedy text differed between two identical requests")
    if facts.get("prefix_hits_from_repeat", 0) < 1:
        bad.append("no prefix-cache hit on the repeated long prompt")
    long_req = next(r for r in traffic.requests if r["id"] == "smoke-d1")
    if long_req.get("prefill_chunks", 0) < 2:
        bad.append("long prompt was not chunk-prefilled "
                   f"({long_req.get('prefill_chunks')} prefill dispatches)")
    for phrase in ("engine step failed", "prewarm failed"):
        if phrase in log_text:
            bad.append(f"engine log contains {phrase!r}")
    attention = health.get("attention") or {}
    want = "pallas" if platform == "tpu" else "xla"
    if attention.get("mode") != want:
        bad.append(f"attention mode is {attention.get('mode')!r}, "
                   f"want {want!r}")
    for op in ("prefill", "paged_decode", "paged_extend"):
        route = (attention.get("traced") or {}).get(op, "")
        if not route.startswith(want):
            bad.append(f"attention op {op} was served by {route!r}")
    return bad


def cache_entries(cache_dir: str) -> dict[str, int]:
    """Compiled programs in the persistent cache, counted by program name
    (an entry is `<name>-<key>`; entries under one name differ in shapes,
    static arguments or how they were lowered)."""
    out: dict[str, int] = {}
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return out
    for n in names:
        if n.endswith("-atime"):
            continue
        prog = n.rsplit("-", 2)[0] if n.endswith("-cache") else n.rsplit("-", 1)[0]
        out[prog] = out.get(prog, 0) + 1
    return out


# ------------------------------------------------------------------ the legs


def start_engine(children: Children, env: dict, preset: str = MODEL,
                 extra_args: tuple[str, ...] = ()) -> tuple[str, float]:
    port = free_port()
    proc = children.start(
        "engine",
        [sys.executable, "-m", "llmlb_tpu.engine.server", "--preset", preset,
         "--port", str(port), *extra_args],
        env)
    base = f"http://127.0.0.1:{port}"
    ready_s = wait_http(f"{base}/api/health", proc, "engine", 900)
    return base, ready_s


def start_gateway(children: Children, engine_base: str,
                  data_dir: str) -> tuple[str, dict]:
    """Gateway up, admin logged in, engine registered, inference key minted
    (.claude/skills/verify/SKILL.md has the same recipe by hand)."""
    port = free_port()
    password = "chip-smoke-admin-1"
    env = {**os.environ, "LLMLB_DATA_DIR": data_dir,
           "LLMLB_LOG_DIR": os.path.join(data_dir, "logs"),
           "LLMLB_ADMIN_PASSWORD": password}
    proc = children.start(
        "gateway",
        [sys.executable, "-m", "llmlb_tpu.gateway.server", "serve",
         "--host", "127.0.0.1", "--port", str(port)],
        env)
    base = f"http://127.0.0.1:{port}"
    wait_http(f"{base}/health", proc, "gateway", 60)
    status, login = http_json("POST", f"{base}/api/auth/login",
                              {"username": "admin", "password": password})
    if status != 200:
        raise SmokeFailure(f"gateway login failed: {status} {login}")
    admin = {"Authorization": f"Bearer {login['token']}"}
    status, ep = http_json("POST", f"{base}/api/endpoints",
                           {"base_url": engine_base, "name": "chip-engine"},
                           headers=admin, timeout=60)
    if status != 201 or ep.get("endpoint_type") != "tpu":
        raise SmokeFailure(f"engine did not register as tpu://: {status} {ep}")
    status, key = http_json("POST", f"{base}/api/api-keys",
                            {"name": "chip-smoke",
                             "permissions": ["openai.inference"]},
                            headers=admin)
    if status not in (200, 201):
        raise SmokeFailure(f"api key not minted: {status} {key}")
    return base, {"Authorization": f"Bearer {key['api_key']}"}


def run_kernel_child(children: Children, env: dict) -> dict:
    proc = children.start(
        "kernels", [sys.executable, os.path.abspath(__file__), "--kernel-leg"],
        env)
    try:
        code = proc.wait(timeout=600)
    except subprocess.TimeoutExpired:
        raise SmokeFailure("kernel leg still running after 600s") from None
    with open(os.path.join(OUT_DIR, "kernels.log"), errors="replace") as f:
        lines = f.read().splitlines()
    report = None
    for line in reversed(lines):
        if line.startswith("KERNEL_LEG "):
            report = json.loads(line[len("KERNEL_LEG "):])
            break
    if code != 0 or report is None:
        tail = "\n".join(lines[-25:])
        raise SmokeFailure(f"kernel leg exited {code}:\n{tail}")
    return report


def main() -> int:
    if "--kernel-leg" in sys.argv:
        return kernel_leg()
    if not os.path.isdir(os.path.join(HERE, "llmlb_tpu")):
        print("chip_smoke.py must run from a checkout of the repository "
              "(llmlb_tpu/ is not beside it)", file=sys.stderr)
        return 2
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    children = Children()
    data_dir = tempfile.mkdtemp(prefix="chip-smoke-gw-")

    def on_signal(signum, _frame):
        raise SmokeFailure(f"stopped by signal {signum}"
                           + (" (deadline)" if signum == signal.SIGALRM else ""))

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGALRM, on_signal)
    signal.alarm(DEADLINE_S)
    try:
        result = smoke(children, data_dir)
    except (SmokeFailure, KeyboardInterrupt) as e:
        note(f"FAILED: {e or type(e).__name__}")
        children.log_tails()
        return 1
    finally:
        signal.alarm(0)
        children.stop()
        shutil.rmtree(data_dir, ignore_errors=True)
        assert "jax" not in sys.modules, "the smoke's parent imported jax"
    report = json.dumps(result)
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        f.write(report + "\n")
    note("passed; report (also in chiprun_out/chip_smoke/report.json):")
    print(report, file=sys.stderr, flush=True)
    print(contract_line(result["device"]), flush=True)
    return 0


def contract_line(device: dict) -> str:
    """What a passing run prints last on stdout: these keys and no others."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def smoke(children: Children, data_dir: str) -> dict:
    chip_env = {**os.environ, **ONE_CHIP_ENV}
    note("kernel leg: Mosaic-compile every Pallas kernel, compare with XLA")
    kernels = run_kernel_child(children, chip_env)
    device = kernels["device"]
    cache_dir = kernels["compile_cache_dir"]
    entries_before = kernels["compile_cache_entries_before"]
    note(f"kernel leg passed on {device['count']} x {device['kind']}")

    # Both servers run `make -C native` at start, which keeps a library
    # that is newer than its sources whatever tree built it: rebuild it
    # here, once, from this tree.
    made = subprocess.run(["make", "-B", "-C", os.path.join(HERE, "native")],
                          capture_output=True, text=True)
    if made.returncode != 0:
        note("native build failed; the servers use their Python paths:\n"
             + made.stderr[-500:])
    note(f"serving leg: engine --preset {MODEL}, default slots and buckets")
    engine_base, ready_s = start_engine(children, chip_env)
    note(f"engine ready after {ready_s:.1f}s")
    gateway_base, key = start_gateway(children, engine_base, data_dir)
    traffic = Traffic(gateway_base, engine_base, key)
    facts = traffic.run()

    _, health = http_json("GET", f"{engine_base}/api/health")
    with open(os.path.join(OUT_DIR, "engine.log"), errors="replace") as f:
        engine_log = f.read()
    with open(os.path.join(OUT_DIR, "engine_health.json"), "w") as f:
        json.dump(health, f, indent=1)
    with open(os.path.join(OUT_DIR, "requests.json"), "w") as f:
        json.dump(traffic.requests, f, indent=1)
    bad = check_engine(health, engine_log, traffic, facts)
    if bad:
        raise SmokeFailure("; ".join(bad))

    entries_after = cache_entries(cache_dir)
    with open(os.path.join(OUT_DIR, "compile_cache.json"), "w") as f:
        json.dump({"dir": cache_dir, "before": entries_before,
                   "after": entries_after}, f, indent=1)
    return {
        "ok": True,
        "device": device,
        "jax": kernels["jax"],
        "libtpu": kernels["libtpu"],
        "model": {"preset": MODEL,
                  "layers": health["engine"]["num_layers"],
                  "params": health["perf"]["n_params"],
                  "weights": "random (preset seed)"},
        "engine_mesh": health["engine"]["mesh"],
        "engine_chip_count": health["tpu"]["chip_count"],
        "hbm_used_bytes": health["tpu"]["hbm_used_bytes"],
        "hbm_total_bytes": health["tpu"]["hbm_total_bytes"],
        "attention": health["attention"],
        "kernels": kernels["kernels"],
        "requests": len(traffic.requests),
        "completion_tokens": sum(r["completion_tokens"]
                                 for r in traffic.requests),
        "engine_errors_total": health["metrics"]["errors_total"],
        "greedy_repeat_identical": facts["greedy_repeat_identical"],
        "greedy_text": facts["greedy_text"],
        "greedy_token_ids": facts["greedy_token_ids"],
        "prefix_hits_from_repeat": facts["prefix_hits_from_repeat"],
        "long_prompt_prefill_chunks": next(
            r["prefill_chunks"] for r in traffic.requests
            if r["id"] == "smoke-d1"),
        "streams_decoding_when_long_prompt_answered":
            facts["c_alive_when_d_answered"],
        # information, not claims
        "time_to_ready_s": round(ready_s, 1),
        "engine_ttft_s": {r["id"]: r["engine_ttft_s"]
                          for r in traffic.requests},
        "request_total_s": {r["id"]: r["total_s"] for r in traffic.requests},
        "compile_cache": {
            "dir": cache_dir,
            "entries_before": sum(entries_before.values()),
            "entries_after": sum(entries_after.values()),
            "entries_by_program": dict(sorted(entries_after.items())),
        },
        "wall_s": round(time.monotonic() - T0, 1),
    }


# -------------------------------------------------- child A: the kernel leg


def kernel_leg() -> int:
    """Compile every Pallas kernel with interpret=False at TinyLlama's
    shapes (H=32, K=4, D=64, pages of 128, block tables for 2048 capacity)
    and compare with the XLA path at the tolerance the interpret-mode tests
    use. Prints `KERNEL_LEG <json>`; exits non-zero unless the backend is
    tpu and every kernel compiled and matched."""
    os.environ["LLMLB_TPU_ATTENTION"] = "xla"  # the dispatchers = reference
    sys.path.insert(0, HERE)
    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    cache_dir = configure_compile_cache()
    entries_before = cache_entries(cache_dir)
    devices = resolve_backend()
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print(f"kernel leg needs a tpu backend, found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 3

    from llmlb_tpu.ops import attention as xla
    from llmlb_tpu.ops import pallas_attention as pa
    from llmlb_tpu.ops.lora import lora_delta_pallas, lora_delta_xla
    from llmlb_tpu.quant import quantize_kv

    H, K, D, PS, PPN, CAP = 32, 4, 64, 128, 16, 2048
    TOL = 2e-2
    bf16 = jnp.bfloat16
    rng = np.random.default_rng(0)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(bf16)

    @functools.lru_cache(maxsize=None)
    def pool(b):
        """A page pool for b rows, its int8 twin, and scattered tables."""
        p = b * PPN + 1
        k_pages, v_pages = rand(p, PS, K, D), rand(p, PS, K, D)
        kq, ks = quantize_kv(np.asarray(k_pages, np.float32))
        vq, vs = quantize_kv(np.asarray(v_pages, np.float32))
        tables = jnp.asarray(
            rng.permutation(np.arange(1, p)).reshape(b, PPN), jnp.int32)
        quant = ({"q": jnp.asarray(kq), "s": jnp.asarray(ks)},
                 {"q": jnp.asarray(vq), "s": jnp.asarray(vs)})
        return k_pages, v_pages, quant, tables

    def stacked(x, layer):
        """`x` as layer `layer` of a pool stacked over two layers, the other
        all zeros: the paged kernels take the stacked pool and read it at
        (layer, page); an int8 pool's scales go in as the layer's slice."""
        layers = [jnp.zeros_like(x), jnp.zeros_like(x)]
        layers[layer] = x
        return jnp.stack(layers)

    results: dict[str, dict] = {}

    def outcome(kernel: str) -> dict:
        return results.setdefault(
            kernel, {"cases": 0, "max_abs_err": 0.0, "ok": True})

    def check(kernel: str, case: str, got, want, valid=None):
        """Record one comparison (allclose at rtol = atol = TOL); only the
        first `valid[b]` rows of batch entry b count — padding rows are
        don't-care."""
        rec = outcome(kernel)
        got = np.asarray(jax.block_until_ready(got), np.float32)
        want = np.asarray(want, np.float32)
        rec["cases"] += 1
        rows = [(got[b, :n], want[b, :n]) for b, n in enumerate(
            [got.shape[1]] * got.shape[0] if valid is None
            else np.asarray(valid))]
        if not all(np.allclose(g, w, rtol=TOL, atol=TOL) for g, w in rows):
            rec["ok"] = False
            rec.setdefault("failed", []).append(case)
        worst = max(float(np.nan_to_num(np.abs(g - w), nan=1e30,
                                        posinf=1e30).max()) for g, w in rows)
        rec["max_abs_err"] = max(rec["max_abs_err"], worst)

    def attempt(kernel: str, case: str, fn):
        try:
            fn()
        except Exception as e:  # a Mosaic refusal is this kernel's outcome
            rec = outcome(kernel)
            rec["ok"] = False
            rec.setdefault("failed", []).append(case)
            rec["error"] = f"{type(e).__name__}: {str(e)[:600]}"

    # decode: one query per row against a 16-page table
    for b in (8, 32):
        q = rand(b, 1, H, D)

        def paged_decode(pages, quant, dead=False):
            # layer 1 of the stacked pool. `dead`: two rows in three are
            # not live (length 0) and must come out zero
            k_pages, v_pages, quant_pools, tables = pool(b)
            k_pages, v_pages = stacked(k_pages, 1), stacked(v_pages, 1)
            qk, qv = ({m: stacked(x, 1) for m, x in qp.items()}
                      for qp in quant_pools)
            lens = jnp.asarray(rng.integers(1, pages * PS + 1, b), jnp.int32)
            if dead:
                lens = jnp.where(jnp.arange(b) % 3 == 1, lens, 0)
            if quant:
                want = xla.paged_attention_decode(q, qk, qv, 1, tables, lens,
                                                  window=pages * PS)
                got = pa.paged_flash_decode_quant(
                    q[:, 0], qk["q"], qk["s"][1], qv["q"], qv["s"][1], 1,
                    tables, lens, pages=pages, interpret=False)
            else:
                want = xla.paged_attention_decode(q, k_pages, v_pages, 1,
                                                  tables, lens,
                                                  window=pages * PS)
                got = pa.paged_flash_decode(q[:, 0], k_pages, v_pages, 1,
                                            tables, lens, pages=pages,
                                            interpret=False)
            check("paged_flash_decode_quant" if quant else
                  "paged_flash_decode", f"B={b},pages={pages},dead={dead}",
                  got, jnp.where((lens > 0)[:, None, None], want[:, 0], 0.0))

        for pages, dead in ((2, False), (PPN, False), (PPN, True)):
            for quant in (False, True):
                attempt("paged_flash_decode_quant" if quant else
                        "paged_flash_decode",
                        f"B={b},pages={pages},dead={dead}",
                        lambda: paged_decode(pages, quant, dead))

    # decode under a LOWER bound a row (models/afmoe.py: Trinity-Mini's
    # window of 2,048 cells as a band of 17 pages of 128 a slot, 32 query
    # heads of 128 on 4 KV heads): layer 1 of a band stacked over two, rows
    # below, at and past the window and at page boundaries, a row in three
    # not live; the work-list names the span's pages alone and the kernel
    # masks at both ends, against the gathered band under the same mask
    for b in (8, 32):
        def band_decode():
            w, r, d = 2048, 17, 128
            band_k = stacked(rand((b + 1) * r, PS, K, d), 1)
            band_v = stacked(rand((b + 1) * r, PS, K, d), 1)
            at = (1, 127, 128, 129, 2047, 2048, 2049, 2175, 2176, 2177, 4351)
            lens = jnp.asarray([at[i % len(at)] for i in range(b)], jnp.int32)
            lens = jnp.where(jnp.arange(b) % 3 == 1, 0, lens)
            low = jnp.maximum(lens - w, 0)
            tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * r
                      + jnp.arange(r, dtype=jnp.int32)[None])
            q = rand(b, 1, H, d)
            want = xla.paged_band_decode(q, band_k, band_v, 1, tables, lens,
                                         low)
            got = pa.paged_flash_decode(
                q[:, 0], band_k, band_v, 1, tables, lens, kv_from=low,
                name=xla.BAND_DECODE, interpret=False)
            check("paged_band_decode", f"B={b}", got,
                  jnp.where((lens > 0)[:, None, None], want[:, 0], 0.0))
            work = pa.decode_work_list(tables, lens, page_size=PS,
                                       kv_from=low)
            pages = jnp.where(lens > 0, (lens - 1) // PS - low // PS + 1, 1)
            check("paged_band_decode", f"B={b},items",
                  jnp.asarray([[float(work.count)]]),
                  jnp.asarray([[float(jnp.sum(pages))]]))

        attempt("paged_band_decode", f"B={b}", band_decode)

    # a GROUP of a row's pages a grid step (PR 54) at the narrowest page
    # the cells serve, Nemotron-3-Nano's 2 KV heads x 16 of 128 (four
    # pages an item by pa.decode_group): rows whose pages are no multiple
    # of four and a row in three not live, bf16 and int8; then the same
    # heads under a lower bound over a band of 17 pages, spans that wrap the
    # band inside a group and start mid-page
    def grouped_decode(quant):
        b, kv, d = 32, 2, 128
        p = b * PPN + 1
        tables = jnp.asarray(
            rng.permutation(np.arange(1, p)).reshape(b, PPN), jnp.int32)
        lens = jnp.asarray(rng.integers(1, PPN * PS + 1, b), jnp.int32)
        lens = jnp.where(jnp.arange(b) % 3 == 1, 0, lens)
        q = rand(b, 1, H, d)
        pools = [rand(p, PS, kv, d) for _ in "kv"]
        name = "paged_flash_decode_quant" if quant else "paged_flash_decode"
        if quant:
            qk, qv = ({m: stacked(jnp.asarray(x), 1) for m, x in zip(
                "qs", quantize_kv(np.asarray(pool, np.float32)))}
                for pool in pools)
            want = xla.paged_attention_decode(q, qk, qv, 1, tables, lens)
            got = pa.paged_flash_decode_quant(
                q[:, 0], qk["q"], qk["s"][1], qv["q"], qv["s"][1], 1, tables,
                lens, interpret=False)
        else:
            k_pages, v_pages = (stacked(pool, 1) for pool in pools)
            want = xla.paged_attention_decode(q, k_pages, v_pages, 1, tables,
                                              lens)
            got = pa.paged_flash_decode(q[:, 0], k_pages, v_pages, 1, tables,
                                        lens, interpret=False)
        check(name, "K=2,group=4", got,
              jnp.where((lens > 0)[:, None, None], want[:, 0], 0.0))
        group = pa.decode_group(PS * kv * 2 * d, PPN)
        work = pa.decode_work_list(tables, lens, page_size=PS, group=group)
        items = jnp.where(lens > 0, -(-(-(-lens // PS)) // group), 1)
        check(name, "K=2,group=4,items",
              jnp.asarray([[float(group), float(work.count)]]),
              jnp.asarray([[4.0, float(jnp.sum(items))]]))

    for quant in (False, True):
        attempt("paged_flash_decode_quant" if quant else "paged_flash_decode",
                "K=2,group=4", functools.partial(grouped_decode, quant))

    def grouped_band():
        b, kv, d, w, r = 32, 2, 128, 2048, 17
        band_k = stacked(rand((b + 1) * r, PS, kv, d), 1)
        band_v = stacked(rand((b + 1) * r, PS, kv, d), 1)
        at = (2049, 2175, 2177, 3000, 4351, 5000, 6001, 127, 1)
        lens = jnp.asarray([at[i % len(at)] for i in range(b)], jnp.int32)
        lens = jnp.where(jnp.arange(b) % 3 == 1, 0, lens)
        low = jnp.maximum(lens - w, 0)
        tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * r
                  + jnp.arange(r, dtype=jnp.int32)[None])
        q = rand(b, 1, H, d)
        want = xla.paged_band_decode(q, band_k, band_v, 1, tables, lens, low)
        got = pa.paged_flash_decode(
            q[:, 0], band_k, band_v, 1, tables, lens, kv_from=low,
            name=xla.BAND_DECODE, interpret=False)
        check("paged_band_decode", "K=2,group=4", got,
              jnp.where((lens > 0)[:, None, None], want[:, 0], 0.0))

    attempt("paged_band_decode", "K=2,group=4", grouped_band)

    # prefill: causal self-attention over a bucketed prompt
    for b, t in ((8, 128), (2, 512)):
        def prefill():
            q, k, v = rand(b, t, H, D), rand(b, t, K, D), rand(b, t, K, D)
            lens = jnp.asarray(rng.integers(t // 2, t + 1, b), jnp.int32)
            want = xla.gqa_attention_prefill(q, k, v, lens)
            got = pa.flash_prefill(q, k, v, lens, interpret=False)
            check("flash_prefill", f"B={b},T={t}", got, want, valid=lens)

        attempt("flash_prefill", f"B={b},T={t}", prefill)

    # extend: a 512-token prefill chunk (a grid step takes its page a KV
    # head at a time), the speculative verify width and a block pass of
    # generation by diffusion, 32 rows x 2 blocks of 4 under the block mask
    # (the page as it is stored, in one masked product: pa.extend_body)
    for b, t, block in ((2, 512, 1), (8, 5, 1), (32, 8, 4)):
        body = pa.extend_body(min(pa.EXTEND_BLOCK_Q, t), H, K, PS)
        case = f"B={b},T={t},block={block},body={body}"
        starts = jnp.asarray(rng.integers(0, (CAP - t) // block, b) * block,
                             jnp.int32)
        chunk = jnp.asarray(rng.integers(max(1, t // 2), t + 1, b), jnp.int32)
        chunk = jnp.maximum(chunk // block * block, block)  # whole blocks
        positions = starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q = rand(b, t, H, D)

        def paged_extend(quant, layer):
            k_pages, v_pages, quant_pools, tables = pool(b)
            k_pages, v_pages = (stacked(k_pages, layer),
                                stacked(v_pages, layer))
            qk, qv = ({m: stacked(x, layer) for m, x in qp.items()}
                      for qp in quant_pools)
            if quant:
                want = xla.paged_attention_extend(q, qk, qv, layer, tables,
                                                  positions, chunk, block)
                got = pa.paged_flash_extend_quant(
                    q, qk["q"], qk["s"][layer], qv["q"], qv["s"][layer],
                    layer, tables, starts, chunk, interpret=False,
                    block=block)
            else:
                want = xla.paged_attention_extend(q, k_pages, v_pages, layer,
                                                  tables, positions, chunk,
                                                  block)
                got = pa.paged_flash_extend(q, k_pages, v_pages, layer,
                                            tables, starts, chunk,
                                            interpret=False, block=block)
            check("paged_flash_extend_quant" if quant else
                  "paged_flash_extend", f"{case},layer={layer}", got, want,
                  valid=chunk)

        for quant in (False, True):
            for layer in (0, 1):
                attempt("paged_flash_extend_quant" if quant else
                        "paged_flash_extend", f"{case},layer={layer}",
                        lambda: paged_extend(quant, layer))

    # LoRA bgmv: decode rows, a prefill chunk, the verify width; through
    # TinyLlama's projections (hidden 2048, kv 256, mlp 5632) at rank 16
    # the latent-attention mixture's two kernels at kanana-2-30b-a3b's
    # widths (models/deepseek_v3.py): absorbed decode over a latent pool
    # (32 heads on one 512-wide latent and a 128-lane rope row), and the
    # grouped expert matmul over stacked experts read at (layer, expert)
    from llmlb_tpu.ops import pallas_moe

    LAT, ROPE = 512, 128
    for b in (8, 64):
        def latent_decode(pages, dead=False):
            p = b * PPN + 1
            c_pages = jnp.stack([jnp.zeros((p, PS, LAT), bf16),
                                 rand(p, PS, LAT)])
            r_pages = jnp.stack([jnp.zeros((p, PS, ROPE), bf16), jnp.pad(
                rand(p, PS, 64), ((0, 0), (0, 0), (0, ROPE - 64)))])
            tables = jnp.asarray(
                rng.permutation(np.arange(1, p)).reshape(b, PPN), jnp.int32)
            q_abs, q_rope = rand(b, 1, H, LAT), rand(b, 1, H, 64)
            lens = rng.integers(1, pages * PS + 1, b)
            if dead:
                lens[::3] = 0
            lens = jnp.asarray(lens, jnp.int32)
            kw = dict(scale=192 ** -0.5)
            want = xla.paged_latent_decode(q_abs, q_rope, c_pages, r_pages, 1,
                                           tables, lens, window=pages * PS,
                                           **kw)[:, 0]
            got = pa.paged_latent_decode(
                q_abs[:, 0], xla._pad_last(q_rope[:, 0], ROPE), c_pages,
                r_pages, 1, tables, lens, pages=pages, interpret=False, **kw)
            live = np.asarray(lens) > 0
            check("paged_latent_decode", f"B={b},pages={pages},dead={dead}",
                  got[live][None], want[live][None])

        for pages, dead in ((PPN, False), (4, False), (PPN, True)):
            attempt("paged_latent_decode", f"B={b},pages={pages},dead={dead}",
                    functools.partial(latent_decode, pages, dead))

    # learned sparse attention's three decode calls at dots3-note-prev's
    # widths (models/dots3_note.py): the indexer's scores read in place from
    # the index keys behind the rope's tile (64 index heads of 128), the
    # absorbed decode over the CHOSEN cells alone (128 heads on a 512-wide
    # latent, a selection a row), and the latent kernel over a ring as a
    # page of 640 cells (64 heads on a 1,024-wide latent, 513 in use)
    def sparse_pools(b):
        """Layer 1 of a latent pool and of a rope pool with the index key
        behind the rope's tile, for b rows, and scattered tables."""
        p = b * PPN + 1
        c_pages = stacked(rand(p, PS, LAT), 1)
        r_pages = stacked(jnp.concatenate([jnp.pad(
            rand(p, PS, 64), ((0, 0), (0, 0), (0, ROPE - 64))),
            rand(p, PS, 128)], axis=-1), 1)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, p)).reshape(b, PPN), jnp.int32)
        return c_pages, r_pages, tables

    def sparse_decode(b=16, heads=128, dead=False):
        c_pages, r_pages, tables = sparse_pools(b)
        lens = rng.integers(1, CAP + 1, b)
        if dead:
            lens[::3] = 0
        lens = jnp.asarray(lens, jnp.int32)
        q_i, w_i = rand(b, 1, 64, 128), rand(b, 1, 64).astype(jnp.float32)
        want = xla.paged_index_scores(q_i, w_i, r_pages, 1, tables)[:, 0]
        got = pa.index_scores_decode(q_i[:, 0], w_i[:, 0], r_pages, 1, tables,
                                     interpret=False)
        seen = jnp.arange(CAP)[None, :] < lens[:, None]
        scale = np.abs(np.asarray(want)).max()
        check("index_scores_decode", f"B={b},dead={dead}",
              jnp.where(seen, got, 0.0)[None] / scale,
              jnp.where(seen, want, 0.0)[None] / scale)
        chosen = xla.topk_mask(want[:, None], seen[:, None], 256)
        q_abs, q_rope = rand(b, 1, heads, LAT), rand(b, 1, heads, 64)
        kw = dict(scale=192 ** -0.5)
        want = xla.paged_latent_decode(q_abs, q_rope, c_pages, r_pages, 1,
                                       tables, lens, selected=chosen,
                                       **kw)[:, 0]
        got = pa.sparse_latent_decode(
            q_abs[:, 0], xla._pad_last(q_rope[:, 0], ROPE), c_pages, r_pages,
            1, tables, lens, chosen[:, 0], interpret=False, **kw)
        live = np.asarray(lens) > 0
        check("sparse_latent_decode", f"B={b},dead={dead}",
              got[live][None], want[live][None])

    for dead in (False, True):
        attempt("sparse_latent_decode", f"B=16,dead={dead}",
                functools.partial(sparse_decode, dead=dead))

    # ... and an extend chunk's attention under the selection: 128 queries
    # x 128 heads a row, rows of different contexts and one with padding
    # queries, against the blocked einsums (valid queries alone count)
    def sparse_extend(b=2, t=128, heads=128):
        c_pages, r_pages, tables = sparse_pools(b)
        starts = jnp.asarray([CAP - t, 300], jnp.int32)
        positions = starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        seen = jnp.arange(CAP)[None, None, :] <= positions[:, :, None]
        chosen = xla.topk_mask(rand(b, t, CAP).astype(jnp.float32), seen, 256)
        q_abs, q_rope = rand(b, t, heads, LAT), rand(b, t, heads, 64)
        lens = jnp.asarray([t, t - 40], jnp.int32)
        want = xla._latent_extend_blocked(
            q_abs, q_rope, c_pages, r_pages, 1, tables, positions, chosen,
            192 ** -0.5)
        got = pa.sparse_latent_extend(
            q_abs, xla._pad_last(q_rope, ROPE), c_pages, r_pages, 1, tables,
            positions, lens, chosen, scale=192 ** -0.5, interpret=False)
        check("sparse_latent_extend", f"B={b},T={t}",
              got.reshape(b, t, -1), want.reshape(b, t, -1), valid=lens)

    attempt("sparse_latent_extend", "B=2,T=128", sparse_extend)

    def window_latent_decode(b=16, heads=64, cells=640, width=1024):
        ring_c, ring_r = rand(3, b + 1, cells, width), rand(3, b + 1, cells,
                                                            ROPE)
        q_abs, q_rope = rand(b, 1, heads, width), rand(b, 1, heads, ROPE)
        slots = jnp.asarray(rng.permutation(b), jnp.int32)
        lens = jnp.asarray(rng.integers(1, 514, b), jnp.int32)
        seen = (jnp.arange(cells)[None, :] < lens[:, None])[:, None, :]
        want = xla._latent_attend(q_abs, q_rope, ring_c[2, slots],
                                  ring_r[2, slots], seen, 256 ** -0.5)[:, 0]
        got = pa.paged_latent_decode(
            q_abs[:, 0], q_rope[:, 0], ring_c, ring_r, 2, slots[:, None],
            lens, scale=256 ** -0.5, interpret=False,
            name="window_latent_decode")
        check("window_latent_decode", f"B={b}", got[None], want[None])

    attempt("window_latent_decode", "B=16", window_latent_decode)

    # a GROUP of a row's pages a grid step in the two kernels whose pools
    # have no head axis (PR 58), by pa.decode_group of a page in both pools:
    # the latent kernel at longcat-flash-omni's 64 heads (four pages an
    # item) and the flat one at mimo-v2-5's 64 heads on 4 x 192 keys and
    # 4 x 128 values (three), rows whose pages are no multiple of the group
    # and a row in three not live
    def grouped_headless(flat):
        from llmlb_tpu.models import mimo_v2

        b, heads, kv, d, dv = 32, 64, 4, 192, 128
        p = b * PPN + 1
        tables = jnp.asarray(
            rng.permutation(np.arange(1, p)).reshape(b, PPN), jnp.int32)
        lens = jnp.asarray(rng.integers(1, PPN * PS + 1, b), jnp.int32)
        lens = jnp.where(jnp.arange(b) % 3 == 1, 0, lens)
        if flat:
            name, widths, want_group = "paged_flat_decode", (kv * d, kv * dv), 3
            k_pages, v_pages = (stacked(rand(p, PS, w), 1) for w in widths)
            q = rand(b, 1, heads, d)
            k, v = (xla.gather_kv_pages(pool, tables, layer=1).reshape(
                b, PPN * PS, kv, -1) for pool in (k_pages, v_pages))
            want = mimo_v2._decode_einsum(q, k, v, lens, None)[:, 0]
            got = pa.paged_flat_decode(q[:, 0], k_pages, v_pages, 1, tables,
                                       lens, num_kv=kv, interpret=False)
        else:
            name, widths, want_group = "paged_latent_decode", (LAT, ROPE), 4
            c_pages = stacked(rand(p, PS, LAT), 1)
            r_pages = stacked(jnp.pad(
                rand(p, PS, 64), ((0, 0), (0, 0), (0, ROPE - 64))), 1)
            q_abs, q_rope = rand(b, 1, heads, LAT), rand(b, 1, heads, 64)
            kw = dict(scale=192 ** -0.5)
            want = xla.paged_latent_decode(q_abs, q_rope, c_pages, r_pages, 1,
                                           tables, lens, **kw)[:, 0]
            got = pa.paged_latent_decode(
                q_abs[:, 0], xla._pad_last(q_rope[:, 0], ROPE), c_pages,
                r_pages, 1, tables, lens, interpret=False, **kw)
        case = f"H={heads},group={want_group}"
        check(name, case, got, jnp.where((lens > 0)[:, None, None], want, 0.0))
        group = pa.decode_group(PS * sum(widths), PPN)
        work = pa.decode_work_list(tables, lens, page_size=PS, group=group)
        items = jnp.where(lens > 0, -(-(-(-lens // PS)) // group), 1)
        check(name, case + ",items",
              jnp.asarray([[float(group), float(work.count)]]),
              jnp.asarray([[float(want_group), float(jnp.sum(items))]]))

    for flat in (False, True):
        attempt("paged_flat_decode" if flat else "paged_latent_decode",
                f"H=64,group={3 if flat else 4}",
                functools.partial(grouped_headless, flat))

    for rows_, k_, o_, tile in ((384, 2048, 768, 32), (384, 768, 2048, 32),
                                (6144, 2048, 768, 32)):
        def gmm():
            experts, layers = 16, 2
            load = rng.multinomial(rows_ - 40, rng.dirichlet(np.ones(experts)))
            load = jnp.asarray(load, jnp.int32)
            a = rand(rows_, k_)
            w = (rand(layers, experts, k_, o_) * k_ ** -0.5).astype(bf16)
            work = pallas_moe.group_work_list(load, rows=rows_, tile=tile)
            got = pallas_moe.grouped_expert_matmul(a, w, 1, work, tile=tile,
                                                   interpret=False)
            want = jax.lax.ragged_dot(a, w[1], load,
                                      preferred_element_type=jnp.float32)
            valid = int(load.sum())  # rows behind the experts': unspecified
            check("grouped_expert_matmul", f"{rows_}x{k_}->{o_}",
                  got[None, :valid], want[None, :valid])

        attempt("grouped_expert_matmul", f"{rows_}x{k_}->{o_}", gmm)

    # a mixture of few rows, multiplied where they stand (PR 63): three
    # matrices whole, two stored output-major at a width no multiple of 128,
    # three in column tiles; half the experts untouched, a quarter of the
    # rows dead
    for rows_, m_, f_, two in ((32, 2304, 1024, False), (64, 2688, 1856, True),
                               (32, 4096, 2048, False)):
        def in_place():
            experts, layers, k = 8, 2, 3
            x = rand(rows_, m_)
            chosen = jnp.asarray(rng.integers(0, experts // 2, (rows_, k)))
            picked = chosen[:, :, None] == jnp.arange(experts)[None, None, :]
            mix = jnp.sum(picked * jnp.asarray(
                rng.uniform(0.1, 1.0, (rows_, k, 1)), jnp.float32), axis=1)
            mix = jnp.where((jnp.arange(rows_) % 4 != 1)[:, None], mix, 0.0)
            up = (layers, experts, f_, m_) if two else (layers, experts, m_, f_)
            wg = None if two else (rand(*up) * m_ ** -0.5).astype(bf16)
            wu = (rand(*up) * m_ ** -0.5).astype(bf16)
            wd = (rand(layers, experts, f_, m_) * f_ ** -0.5).astype(bf16)
            count, expert_of = pallas_moe.touched_experts(
                jnp.sum(mix != 0, axis=0))
            got = pallas_moe.expert_rows_in_place(
                x, mix, wg, wu, wd, 1, count, expert_of, act=jax.nn.silu,
                transposed=two, interpret=False)
            spec = "sm,xfm->xsf" if two else "sm,xmf->xsf"
            h = jax.nn.silu(jnp.einsum(
                spec, x, wu[1] if two else wg[1],
                preferred_element_type=jnp.float32).astype(bf16))
            if not two:
                h = h * jnp.einsum(spec, x, wu[1],
                                   preferred_element_type=jnp.float32
                                   ).astype(bf16)
            y = jnp.einsum("xsf,xfm->xsm", h, wd[1],
                           preferred_element_type=jnp.float32)
            check("grouped_expert_matmul", f"in place,{rows_}x{m_}x{f_}",
                  got[None], jnp.einsum("xsm,sx->sm", y, mix)[None])

        attempt("grouped_expert_matmul", f"in place,{rows_}x{m_}x{f_}",
                in_place)

    # the delta rule's step kernel at Olmo-Hybrid-7B's heads (30 heads, keys
    # of 96, values of 192: a float32 state [96, 5760] a slot), layer 1 of a
    # pool stacked over two, a row in three not live: the output, and the
    # whole pool (the other layer and the rows not live must not move)
    from llmlb_tpu.ops import delta_rule

    for slots in (8, 32):
        def rule_step():
            def f32(*shape):
                return jnp.asarray(rng.normal(size=shape), jnp.float32)

            q, k = f32(slots, 30, 96) * 96 ** -0.5, f32(slots, 30, 96)
            k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
            v = f32(slots, 30, 192)
            alpha = jnp.asarray(rng.uniform(0.5, 1.0, (slots, 30)), jnp.float32)
            beta = jnp.asarray(rng.uniform(0.0, 2.0, (slots, 30)), jnp.float32)
            pool_ = f32(2, slots, 96, 30 * 192)
            live = jnp.arange(slots) % 3 != 1
            want_o, want = delta_rule.delta_rule_step(  # the jax.numpy route
                pool_, 1, q, k, v, alpha, beta, live=live)
            got, o = delta_rule.delta_rule_decode_step(
                pool_ + 0, 1, q, jnp.where(live[:, None, None], k, 0.0), v,
                jnp.where(live[:, None], alpha, 1.0),
                jnp.where(live[:, None], beta, 0.0), interpret=False)
            check("delta_rule_step", f"slots={slots},out", o[live][None],
                  want_o[live][None])
            check("delta_rule_step", f"slots={slots},pool",
                  got.reshape(1, 2 * slots, -1), want.reshape(1, 2 * slots, -1))
            unmoved = np.array_equal(np.asarray(got[0]), np.asarray(pool_[0])) \
                and np.array_equal(np.asarray(got[1][~live]),
                                   np.asarray(pool_[1][~live]))
            check("delta_rule_step", f"slots={slots},rows not live unmoved",
                  jnp.asarray([[float(unmoved)]]), jnp.asarray([[1.0]]))

        attempt("delta_rule_step", f"slots={slots}", rule_step)

    # the same kernel with a decay a KEY CHANNEL at Kimi-Linear's heads (32
    # heads, keys and values of 128: a float32 state [128, 4096] a slot, a
    # block a head): the decay travels with q and k down the sublanes
    def kda_step(slots=32):
        def f32(*shape):
            return jnp.asarray(rng.normal(size=shape), jnp.float32)

        q, k = f32(slots, 32, 128) * 128 ** -0.5, f32(slots, 32, 128)
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = f32(slots, 32, 128)
        alpha = jnp.asarray(rng.uniform(0.2, 1.0, (slots, 32, 128)),
                            jnp.float32)
        beta = jnp.asarray(rng.uniform(0.0, 1.0, (slots, 32)), jnp.float32)
        pool_ = f32(2, slots, 128, 32 * 128)
        live = jnp.arange(slots) % 3 != 1
        want_o, want = delta_rule.delta_rule_step(  # the jax.numpy route
            pool_, 1, q, k, v, alpha, beta, live=live)
        got, o = delta_rule.delta_rule_decode_step(
            pool_ + 0, 1, q, jnp.where(live[:, None, None], k, 0.0), v,
            jnp.where(live[:, None, None], alpha, 1.0),
            jnp.where(live[:, None], beta, 0.0), interpret=False)
        check("kda_step", "slots=32,out", o[live][None], want_o[live][None])
        check("kda_step", "slots=32,pool", got.reshape(1, 2 * slots, -1),
              want.reshape(1, 2 * slots, -1))
        unmoved = np.array_equal(np.asarray(got[0]), np.asarray(pool_[0])) \
            and np.array_equal(np.asarray(got[1][~live]),
                               np.asarray(pool_[1][~live]))
        check("kda_step", "slots=32,rows not live unmoved",
              jnp.asarray([[float(unmoved)]]), jnp.asarray([[1.0]]))

    attempt("kda_step", "slots=32", kda_step)

    # the state-space step kernel at ONE group of B and C for all 64 heads
    # (Granite-4.0-H) and at Nemotron-3-Nano's 8 groups — one body, a group's
    # rows chosen a turn of its loop — layer 1 of a pool stacked over two, a
    # row in three not live: the output, and the whole pool
    from llmlb_tpu.ops import ssm

    for groups in (1, 8):
        def state_step(slots=32, heads=64, p=64, n=128):
            def f32(*shape):
                return jnp.asarray(rng.normal(size=shape), jnp.float32)

            x, bm, cm = (f32(slots, heads, p), f32(slots, groups, n),
                         f32(slots, groups, n))
            dt = jnp.asarray(rng.uniform(0.001, 0.1, (slots, heads)),
                             jnp.float32)
            a = -jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32)
            pool_ = f32(2, slots, heads, p, n)
            live = jnp.arange(slots) % 3 != 1
            decay, dtx = ssm._step_inputs(x, dt, a, live)
            # the jax.numpy route: slots named, so no kernel
            want_y, want = ssm.ssm_step(
                x, dt, a, bm, cm, jnp.zeros(heads), pool_, 1,
                slots=jnp.arange(slots), live=live)
            got, y = ssm.ssm_decode_step(pool_ + 0, 1, decay, dtx, bm, cm,
                                         interpret=False)
            case = f"groups={groups}"
            check("ssm_decode_step", case + ",out", y.reshape(1, slots, -1),
                  want_y.reshape(1, slots, -1))
            check("ssm_decode_step", case + ",pool",
                  got.reshape(1, 2 * slots, -1), want.reshape(1, 2 * slots, -1))
            unmoved = np.array_equal(np.asarray(got[0]), np.asarray(pool_[0])) \
                and np.array_equal(np.asarray(got[1][~live]),
                                   np.asarray(pool_[1][~live]))
            check("ssm_decode_step", case + ",rows not live unmoved",
                  jnp.asarray([[float(unmoved)]]), jnp.asarray([[1.0]]))

        attempt("ssm_decode_step", f"groups={groups}", state_step)

    # 8 KV heads of 64 (Granite-4.0-H): a fresh prompt through flash_prefill
    # as it is; decode and extend over a pool whose rows hold two heads side
    # by side ([.., 4, 128], ops/attention.lane_pack: no lane is padding),
    # the queries in their own head's lanes, against the XLA arm over the
    # same numbers as [.., 8, 64]
    def narrow_heads(b=8, k8=8, d64=64, pages=4):
        f = xla.lane_pack(k8, d64)
        p = b * pages + 1
        kp, vp = rand(2, p, PS, k8, d64), rand(2, p, PS, k8, d64)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, p)).reshape(b, pages), jnp.int32)
        lens = jnp.asarray(rng.integers(1, pages * PS + 1, b), jnp.int32)
        q = rand(b, 1, H, d64)
        want = xla.gqa_attention_decode(
            q, xla.gather_kv_pages(kp, tables, layer=1),
            xla.gather_kv_pages(vp, tables, layer=1), lens)
        got = xla.unpack_heads(pa.paged_flash_decode(
            xla.pack_queries(q, k8, f)[:, 0], xla.pack_kv(kp, f),
            xla.pack_kv(vp, f), 1, tables, lens, pages=pages,
            interpret=False)[:, None], k8, f)
        check("paged_flash_decode", f"8x64 packed by {f}", got, want)
        t = 64
        starts = jnp.asarray(rng.integers(0, (pages - 1) * PS, b), jnp.int32)
        chunk = jnp.asarray(rng.integers(1, t + 1, b), jnp.int32)
        q = rand(b, t, H, d64)
        pos = starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        want = xla.gqa_attention_extend(
            q, xla.gather_kv_pages(kp, tables, layer=1),
            xla.gather_kv_pages(vp, tables, layer=1), pos)
        got = xla.unpack_heads(pa.paged_flash_extend(
            xla.pack_queries(q, k8, f), xla.pack_kv(kp, f),
            xla.pack_kv(vp, f), 1, tables, starts, chunk, interpret=False),
            k8, f)
        check("paged_flash_extend", f"8x64 packed by {f}", got, want,
              valid=chunk)
        k, v = rand(b, 256, k8, d64), rand(b, 256, k8, d64)
        q = rand(b, 256, H, d64)
        lens = jnp.asarray(rng.integers(1, 257, b), jnp.int32)
        check("flash_prefill", "8x64",
              pa.flash_prefill(q, k, v, lens, interpret=False),
              xla._prefill_einsum(q, k, v, lens), valid=lens)

    attempt("paged_flash_decode", "8x64 packed", narrow_heads)

    # 64 experts of [2048, 1536] held whole (LFM2-24B-A2B, models/
    # lfm2_moe.py): the grouped products at a shape no other family has, at
    # a decode step's 32 rows x 4 (most experts a row or two, some none) and
    # at a prefill's 1,024 x 4, layer 1 of a stack of two
    for rows_, k_, o_ in ((128, 2048, 1536), (128, 1536, 2048),
                          (4096, 2048, 1536)):
        def whole_mixture(experts=64, tile=pallas_moe.ROW_TILE):
            load = jnp.asarray(rng.multinomial(
                rows_ - 8, rng.dirichlet(np.ones(experts))), jnp.int32)
            a = rand(rows_, k_)
            w = (rand(2, experts, k_, o_) * k_ ** -0.5).astype(bf16)
            work = pallas_moe.group_work_list(load, rows=rows_, tile=tile)
            got = pallas_moe.grouped_expert_matmul(a, w, 1, work, tile=tile,
                                                   interpret=False)
            want = jax.lax.ragged_dot(a, w[1], load,
                                      preferred_element_type=jnp.float32)
            valid = int(load.sum())  # rows behind the experts': unspecified
            check("grouped_expert_matmul", f"64 experts,{rows_}x{k_}->{o_}",
                  got[None, :valid], want[None, :valid])

        attempt("grouped_expert_matmul", f"64 experts,{rows_}x{k_}->{o_}",
                whole_mixture)

    n_adapters, rank = 9, 16
    for b, t in ((8, 1), (32, 1), (2, 512), (8, 5)):
        for n_in, n_out in ((2048, 2048), (2048, 256), (2048, 5632),
                            (5632, 2048)):
            def bgmv():
                x = rand(b, t, n_in)
                a = (rand(n_adapters, n_in, rank) * n_in ** -0.5).astype(bf16)
                bb = (rand(n_adapters, rank, n_out) * rank ** -0.5).astype(bf16)
                idx = jnp.asarray(rng.integers(0, n_adapters, b), jnp.int32)
                want = lora_delta_xla(x, a, bb, idx)
                got = lora_delta_pallas(x, a, bb, idx, interpret=False)
                check("lora_delta_pallas", f"B={b},T={t},{n_in}->{n_out}",
                      got, want)

            attempt("lora_delta_pallas", f"B={b},T={t},{n_in}->{n_out}", bgmv)

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    for rec in results.values():
        rec["max_abs_err"] = round(rec["max_abs_err"], 5)
    print("KERNEL_LEG " + json.dumps({
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "jax": jax.__version__,
        "libtpu": libtpu_version,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_before": entries_before,
        "kernels": results,
    }), flush=True)
    return 0 if all(r["ok"] for r in results.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
