#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the repo root (the package is not pip-installed):

    python3 chip_smoke.py              # one TPU chip, what the driver runs
    python3 chip_smoke.py --chips 4    # the tensor-parallel path, and only it
    python3 chip_smoke.py --rehearse [--chips 4]   # CPU, tiny model; no chip

It drives the main path once through the entry points a user would call,
at the full width and depth of llama3-8b (int8 weights + int8 KV, weights
drawn from --seed): `python -m generativeaiexamples_tpu.serving --model-size
8b` and `python -m generativeaiexamples_tpu.api.server`, with the parent
as their HTTP client.

THIS process never imports JAX (nor the package): a chip belongs to one
process at a time. It runs children one after another, each the only
holder of the chip while it lives — the chain server child is pinned to
the CPU. Any child's non-zero exit, time-out or failed check ends the
run non-zero at once; no phase is caught and carried past. Everything
worth reading is printed on earlier lines; the last line of a passing
run is `{"ok": true, "device": {"platform", "kind", "count"}}` as the
engine server's /health reports the device, never assumed. Without
--rehearse a platform other than "tpu" fails; --rehearse says "cpu" and
is never a chip run.

Phases on one chip:
  device-ops  one child: every Pallas kernel of the served path compiled
              (not interpreted) at llama3-8b / arctic-embed-l widths
              against its XLA reference, and flat + IVF top-k over a
              seeded 200k x 1024 corpus against exact numpy top-k.
  boot 1      the engine server boots (warm-up on) and is stopped.
  boot 2      it boots again: its warm-up must come from the persistent
              compile cache (no step program compiled twice).
  serve       chain server child (JAX_PLATFORMS=cpu) + the requests;
              zero compiles after warm-up, every decode/prefill step
              program carries its Pallas kernel (`tpu_custom_call`), no
              kernel declined on the TPU.
Logs land in chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import uuid

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
IR_DIR = os.path.join(ROOT, ".chip_smoke_ir")  # dumps are large: not sent back
T0 = time.monotonic()

# The served profile, set ONLY through existing APP_ENGINE_* variables.
# llama3-8b int8 (7.97 GiB) + int8 KV pool (289 pages, 2.33 GiB) +
# arctic-embed-l and the reranker in bf16 (~0.9 GiB) on a 16 GB chip; the
# step programs' memory_analysis() peaks at 11.2 GiB (CHANGES.md, PR 22).
# Narrowed for a cold run well inside the time limit: two prefill
# buckets, prefill groups of one, decode blocks of 1 and 2 steps — ten
# 32-layer programs instead of ~28. Warm-up stays ON.
ENGINE_PROFILE = {
    "APP_ENGINE_QUANTIZEWEIGHTS": "int8",
    "APP_ENGINE_KVDTYPE": "int8",
    "APP_ENGINE_MAXBATCHSIZE": "8",
    "APP_ENGINE_MAXSEQLEN": "4096",
    "APP_ENGINE_PAGESIZE": "128",
    "APP_ENGINE_PREFILLBUCKETS": "[128, 2048]",
    "APP_ENGINE_MAXPREFILLGROUP": "1",
    "APP_ENGINE_DECODESTEPSPERDISPATCH": "2",
}
REHEARSE_PROFILE = dict(ENGINE_PROFILE, APP_ENGINE_MAXSEQLEN="2048",
                        APP_ENGINE_MAXBATCHSIZE="4")
STEP_PROGRAMS = ("prefill_batch_step", "decode_multi_step")
NEW_TOKENS = 64
BOOT_TIMEOUT_S = 900.0  # a cold llama3-8b boot measured 313 s (PR 22)

_children: list = []


# -- plumbing ----------------------------------------------------------------


def say(msg: str) -> None:
    print(f"[smoke {time.monotonic() - T0:7.1f}s] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One server child in its own process group, output to a log file."""

    def __init__(self, name: str, cmd: list, env: dict):
        self.name = name
        self.log_path = os.path.join(OUT, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)
        _children.append(self)

    def log_text(self) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as fh:
            return fh.read()

    def tail(self, n: int = 40) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
                self.proc.wait(timeout=20)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=20)
        self._log.close()
        if self in _children:
            _children.remove(self)


def stop_all() -> None:
    for c in list(_children):
        c.stop()


def http(method: str, url: str, body=None, *, headers=None,
         timeout: float = 120.0):
    """(status, bytes). A refused connection raises."""
    data = body
    headers = dict(headers or {})
    if body is not None and not isinstance(body, bytes):
        data = json.dumps(body).encode()
        headers.setdefault("Content-Type", "application/json")
    req = urllib.request.Request(url, data=data, method=method,
                                 headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def get_json(url: str, timeout: float = 30.0) -> dict:
    status, raw = http("GET", url, timeout=timeout)
    check(status == 200, f"GET {url} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def wait_healthy(child: Child, url: str, timeout_s: float) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        check(child.proc.poll() is None,
              f"{child.name} exited with code {child.proc.returncode} "
              f"before it was healthy:\n{child.tail()}")
        try:
            status, _ = http("GET", url, timeout=5)
            if status == 200:
                return time.monotonic() - t0
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(1.0)
    raise SmokeFailure(f"{child.name} not healthy within {timeout_s:.0f}s:\n"
                       f"{child.tail()}")


def base_env(args) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("APP_", "ENGINE_"))}
    env["PYTHONUNBUFFERED"] = "1"
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.chips}"
        ).strip()
    return env


def engine_env(args) -> dict:
    return dict(base_env(args),
                **(REHEARSE_PROFILE if args.rehearse else ENGINE_PROFILE))


def run_child(role: str, args, timeout_s: float) -> None:
    """A chip-holding child of this same file; its lines are ours."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", role,
           "--seed", str(args.seed), "--chips", str(args.chips)]
    if args.rehearse:
        cmd.append("--rehearse")
    say(f"phase {role}: start")
    t0 = time.monotonic()
    try:
        rc = subprocess.run(cmd, cwd=ROOT, env=engine_env(args),
                            timeout=timeout_s).returncode
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"phase {role} timed out after {timeout_s:.0f}s")
    check(rc == 0, f"phase {role} exited with code {rc}")
    say(f"phase {role}: ok in {time.monotonic() - t0:.1f}s")


# -- the engine server -------------------------------------------------------


def boot_engine(args, name: str):
    """Start the engine server, wait for /health. Returns (child, url,
    seconds to healthy)."""
    port = free_port()
    ir_dir = os.path.join(IR_DIR, name)
    shutil.rmtree(ir_dir, ignore_errors=True)
    env = engine_env(args)
    env.update({
        # Compile log (the zero-compile test's technique), cache hits and
        # misses by program name, and each program's lowered text.
        "JAX_LOG_COMPILES": "1",
        "JAX_EXPLAIN_CACHE_MISSES": "1",
        "JAX_DUMP_IR_TO": ir_dir,
    })
    cmd = [sys.executable, "-m", "generativeaiexamples_tpu.serving",
           "--host", "127.0.0.1", "--port", str(port),
           "--model-size", "tiny" if args.rehearse else "8b",
           "--seed", str(args.seed)]
    child = Child(name, cmd, env)
    url = f"http://127.0.0.1:{port}"
    took = wait_healthy(child, url + "/health", BOOT_TIMEOUT_S)
    return child, url, took


_KEY = r"'(jit_[\w.]+?)' with key '([^']+)'"


def cache_events(log: str) -> dict:
    """{"hit"|"miss"|"written": {cache key: program}} from one boot's log
    (JAX prints each line through two handlers: keyed, so deduplicated)."""
    out = {"hit": {}, "miss": {}, "written": {}}
    for m in re.finditer(r"Persistent compilation cache hit for " + _KEY, log):
        out["hit"][m.group(2)] = m.group(1)
    for m in re.finditer(r"PERSISTENT COMPILATION CACHE MISS for " + _KEY,
                         log):
        out["miss"][m.group(2)] = m.group(1)
    for m in re.finditer(r"Writing (jit_[\w.]+) to persistent compilation "
                         r"cache with key '([^']+)'", log):
        out["written"][m.group(2)] = m.group(1)
    return out


def is_step_program(program: str) -> bool:
    return any(s in program for s in STEP_PROGRAMS)


def warmup_seconds(log: str) -> str:
    found = re.findall(r"(engine|encoder) warm-up done in ([\d.]+)s", log)
    return ", ".join(f"{k} {v}s" for k, v in dict(found).items()) or "?"


def check_cache(first: dict, second: dict, rehearse: bool) -> None:
    """The second boot must read back whatever the first one left in
    the cache; on the chip that is every step program."""
    kept = dict(first["written"], **first["hit"])
    again = {k: p for k, p in second["miss"].items() if k in kept}
    check(not again, f"second boot recompiled {len(again)} programs the "
          f"first boot had cached: {sorted(set(again.values()))}")
    step_hits = [p for p in second["hit"].values() if is_step_program(p)]
    step_miss = [p for p in second["miss"].values() if is_step_program(p)]
    say(f"cache: boot 2 hits {len(second['hit'])} (step programs "
        f"{len(step_hits)}), misses {len(second['miss'])} (step programs "
        f"{len(step_miss)}); boot 1 wrote {len(first['written'])}, hit "
        f"{len(first['hit'])}")
    check(step_hits, "second boot read no step program from the cache")
    if not rehearse:
        # (at tiny size on the CPU most programs compile in under the
        # 0.5 s threshold and are rightly never written)
        check(not step_miss, f"second boot compiled step programs again: "
              f"{sorted(set(step_miss))}")


def check_step_programs(name: str, rehearse: bool) -> None:
    """Every decode and prefill step program the server lowered carries
    its Pallas kernel; a program without `tpu_custom_call` is the silent
    reference path showing."""
    for prog in STEP_PROGRAMS:
        files = sorted(glob.glob(os.path.join(
            IR_DIR, name, f"*jit_{prog}*.mlir")))
        check(files, f"no lowered text dumped for {prog}")
        counts = []
        for path in files:
            with open(path, errors="replace") as fh:
                counts.append(fh.read().count("tpu_custom_call"))
        say(f"step program {prog}: {len(files)} variants, tpu_custom_call "
            f"per variant {counts}")
        if not rehearse:
            check(all(counts), f"{prog}: a variant holds no tpu_custom_call "
                  f"({counts}) — the XLA reference route was taken")


# -- the requests ------------------------------------------------------------


def sse_events(url: str, body: dict, timeout: float = 300.0) -> list:
    """POST and read a text/event-stream to its end; the data payloads."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        check(resp.status == 200, f"POST {url} -> {resp.status}")
        for raw in resp:
            line = raw.decode(errors="replace").strip()
            if line.startswith("data: "):
                events.append(line[6:])
    return events


def long_prompt(n_chars: int) -> str:
    words = ("retrieval", "augmented", "generation", "serves", "documents",
             "through", "a", "paged", "cache", "on", "one", "chip")
    out, i = [], 0
    while sum(len(w) + 1 for w in out) < n_chars:
        out.append(words[i % len(words)])
        i += 1
    return " ".join(out)


def chat_requests(engine_url: str) -> int:
    """4 /v1/chat/completions in flight together: 2 streamed, 2 not; one
    prompt >= 1k tokens (the second prefill bucket), greedy and sampled.
    Returns the tokens asked for."""
    bodies = [
        {"stream": False, "temperature": 0.0,
         "messages": [{"role": "user", "content": "What is a paged KV "
                       "cache, in one paragraph?"}]},
        {"stream": False, "temperature": 0.7, "top_p": 0.9,
         "messages": [{"role": "user", "content": long_prompt(1200)}]},
        {"stream": True, "temperature": 0.0,
         "messages": [{"role": "user", "content": "Name three uses of an "
                       "embedding model."}]},
        {"stream": True, "temperature": 0.7, "top_p": 0.9,
         "messages": [{"role": "system", "content": "You are terse."},
                      {"role": "user", "content": "Why quantize weights "
                       "to int8?"}]},
    ]
    results: list = [None] * len(bodies)

    def one(i: int) -> None:
        body = dict(bodies[i], model="llama3-8b-instruct",
                    max_tokens=NEW_TOKENS)
        try:
            if body["stream"]:
                results[i] = sse_events(
                    engine_url + "/v1/chat/completions", body)
            else:
                status, raw = http("POST",
                                   engine_url + "/v1/chat/completions",
                                   body, timeout=300)
                results[i] = (status, raw)
        except Exception as e:  # re-raised on the main thread below
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(bodies))]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    for i, res in enumerate(results):
        check(res is not None, f"chat {i} did not finish")
        if isinstance(res, Exception):
            raise SmokeFailure(f"chat {i} failed: {res!r}")
        if bodies[i]["stream"]:
            check(res and res[-1] == "[DONE]",
                  f"chat {i}: stream did not end with [DONE]: {res[-3:]}")
            last = json.loads(res[-2])
            finish = last["choices"][0]["finish_reason"]
            check(finish == "length",
                  f"chat {i}: streamed finish_reason {finish!r}")
            say(f"chat {i} (stream): {len(res) - 2} content frames, "
                f"finish_reason {finish}")
        else:
            status, raw = res
            check(status == 200, f"chat {i} -> {status}: {raw[:300]!r}")
            out = json.loads(raw)
            usage = out["usage"]
            check(usage["completion_tokens"] == NEW_TOKENS,
                  f"chat {i}: asked {NEW_TOKENS} tokens, got {usage}")
            say(f"chat {i}: prompt {usage['prompt_tokens']} tokens, "
                f"completion {usage['completion_tokens']}, finish_reason "
                f"{out['choices'][0]['finish_reason']}")
    check(any(json.loads(r[1])["usage"]["prompt_tokens"] >= 1000
              for r, b in zip(results, bodies) if not b["stream"]),
          "no prompt reached 1k tokens")
    say(f"4 chat completions in {time.monotonic() - t0:.1f}s")
    return NEW_TOKENS * len(bodies)


def encoder_requests(engine_url: str, dim: int) -> None:
    texts = ["a paged KV cache stores keys and values in fixed pages",
             "the chain server retrieves context before generation",
             long_prompt(400), "tpu"]
    status, raw = http("POST", engine_url + "/v1/embeddings",
                       {"model": "snowflake-arctic-embed-l", "input": texts})
    check(status == 200, f"/v1/embeddings -> {status}: {raw[:300]!r}")
    data = json.loads(raw)["data"]
    check(len(data) == len(texts), f"{len(data)} embeddings for "
          f"{len(texts)} inputs")
    for row in data:
        vec = row["embedding"]
        check(len(vec) == dim, f"embedding dimension {len(vec)} != {dim}")
        check(all(v == v and abs(v) < 1e6 for v in vec),
              "embedding holds a non-finite value")
    say(f"/v1/embeddings: {len(data)} x {dim}, finite")
    passages = [{"text": t} for t in texts]
    status, raw = http("POST", engine_url + "/v1/ranking",
                       {"model": "rerank", "query": {"text": "what is a "
                        "paged cache"}, "passages": passages})
    check(status == 200, f"/v1/ranking -> {status}: {raw[:300]!r}")
    ranks = json.loads(raw)["rankings"]
    check(sorted(r["index"] for r in ranks) == list(range(len(texts))),
          f"/v1/ranking returned {ranks}")
    check(all(r["logit"] == r["logit"] for r in ranks),
          "ranking logit is NaN")
    say(f"/v1/ranking: {len(ranks)} passages ranked")


def multipart(field: str, filename: str, payload: bytes):
    boundary = uuid.uuid4().hex
    body = (f"--{boundary}\r\nContent-Disposition: form-data; "
            f'name="{field}"; filename="{filename}"\r\n'
            f"Content-Type: text/markdown\r\n\r\n").encode() + payload + \
        f"\r\n--{boundary}--\r\n".encode()
    return body, {"Content-Type": f"multipart/form-data; boundary={boundary}"}


def chain_requests(chain_url: str) -> int:
    """Upload, search, and two RAG answers read to [DONE]. Returns the
    number of /generate calls that reached the LLM."""
    with open(os.path.join(ROOT, "docs", "architecture.md"), "rb") as fh:
        body, headers = multipart("file", "architecture.md", fh.read())
    t0 = time.monotonic()
    status, raw = http("POST", chain_url + "/documents", body,
                       headers=headers, timeout=600)
    check(status == 200, f"/documents -> {status}: {raw[:300]!r}")
    say(f"/documents: architecture.md ingested in "
        f"{time.monotonic() - t0:.1f}s")
    status, raw = http("POST", chain_url + "/search",
                       {"query": "how is the KV cache paged", "top_k": 4})
    check(status == 200, f"/search -> {status}: {raw[:300]!r}")
    chunks = json.loads(raw)["chunks"]
    check(len(chunks) == 4 and all(c["content"] for c in chunks),
          f"/search returned {len(chunks)} chunks")
    say(f"/search: {len(chunks)} chunks, scores "
        f"{[round(c['score'], 3) for c in chunks]}")
    questions = ["How does the engine batch requests?",
                 "Which component retrieves documents?"]
    for q in questions:
        t0 = time.monotonic()
        events = sse_events(chain_url + "/generate", {
            "messages": [{"role": "user", "content": q}],
            "use_knowledge_base": True, "max_tokens": NEW_TOKENS})
        check(events, "/generate returned no frame")
        frames = [json.loads(e) for e in events]
        check(frames[-1]["choices"][0]["finish_reason"] == "[DONE]",
              f"/generate did not end with [DONE]: {events[-1][:200]}")
        text = "".join(f["choices"][0]["message"]["content"]
                       for f in frames)
        check("Error from chain server" not in text
              and "No response generated" not in text,
              f"/generate did not reach the LLM: {text[:200]!r}")
        say(f"/generate (RAG): {len(frames)} frames to [DONE] in "
            f"{time.monotonic() - t0:.1f}s")
    return len(questions)


def chain_env(args, engine_url: str, dim: int) -> dict:
    """The chain server of deploy/compose/rag-app-text-chatbot.yaml:
    every connector remote, the store host-side, pinned to the CPU."""
    env = base_env(args)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "EXAMPLE_NAME": "developer_rag",
        "APP_LLM_MODELENGINE": "openai",
        "APP_LLM_SERVERURL": engine_url + "/v1",
        "APP_LLM_MODELNAME": "llama3-8b-instruct",
        "APP_EMBEDDINGS_MODELENGINE": "openai",
        "APP_EMBEDDINGS_SERVERURL": engine_url + "/v1",
        "APP_EMBEDDINGS_MODELNAME": "snowflake-arctic-embed-l",
        "APP_EMBEDDINGS_DIMENSIONS": str(dim),
        "APP_VECTORSTORE_NAME": "memory",
        "APP_RETRIEVER_TOPK": "4",
        # Random weights make similarity scores noise, and the hermetic
        # byte tokenizer spends a token per byte: open the threshold and
        # size the context so the RAG prompt stays inside the warmed
        # 2048-token bucket.
        "APP_RETRIEVER_SCORETHRESHOLD": "-1.0",
        "APP_RETRIEVER_MAXCONTEXTTOKENS": "160",
        "APP_TEXTSPLITTER_CHUNKSIZE": "60",
        "APP_TEXTSPLITTER_CHUNKOVERLAP": "10",
    })
    return env


def compiles_after_warmup(log: str) -> list:
    marker = log.rfind("engine server on ")
    check(marker >= 0, "engine log has no 'engine server on' line")
    return sorted(set(re.findall(r"Compiling ([\w.<>]+)", log[marker:])))


def device_of(health: dict, rehearse: bool) -> dict:
    device = {"platform": health["platform"], "kind": health["device_kind"],
              "count": health["devices"]}
    if rehearse:
        check(device["platform"] != "tpu",
              "--rehearse must never run on the chip")
    else:
        check(device["platform"] == "tpu",
              f"the engine server runs on {device['platform']!r}, not on a "
              f"TPU")
    return device


def serve_phase(args, engine: Child, engine_url: str, *,
                with_chain: bool) -> dict:
    health = get_json(engine_url + "/health")
    device = device_of(health, args.rehearse)
    say(f"engine /health: {device}, memory {health['device_memory']}")
    check(all(health["engines"].values()),
          f"engine server lacks an engine: {health['engines']}")
    before = get_json(engine_url + "/metrics")["tokens_generated"]
    asked = chat_requests(engine_url)
    if with_chain:
        dim = 32 if args.rehearse else 1024
        encoder_requests(engine_url, dim)
        port = free_port()
        chain = Child("chain-server", [
            sys.executable, "-m", "generativeaiexamples_tpu.api.server",
            "--host", "127.0.0.1", "--port", str(port)],
            chain_env(args, engine_url, dim))
        chain_url = f"http://127.0.0.1:{port}"
        took = wait_healthy(chain, chain_url + "/health", 120)
        say(f"chain server healthy in {took:.1f}s beside the engine server "
            f"(JAX_PLATFORMS=cpu)")
        asked += NEW_TOKENS * chain_requests(chain_url)
        chain.stop()
    metrics = get_json(engine_url + "/metrics")
    made = metrics["tokens_generated"] - before
    say(f"engine /metrics: tokens_generated +{made} (asked {asked}), "
        f"prefill_tokens {metrics['prefill_tokens']}")
    check(made >= asked, f"/metrics shows {made} new tokens, {asked} asked")
    health = get_json(engine_url + "/health")
    check(health["status"] == "healthy", f"/health after the load: {health}")
    say(f"engine /health after the load: healthy, memory "
        f"{health['device_memory']}")
    log = engine.log_text()
    late = compiles_after_warmup(log)
    check(not late, f"{len(late)} programs compiled after warm-up: {late}")
    say("zero compiles after warm-up")
    declined = sorted(set(re.findall(r"kernel declined: .*", log)))
    for line in declined:
        say(line)
    check(args.rehearse or not declined,
          "a Pallas kernel was declined on the TPU (lines above)")
    return device


# -- the runs ----------------------------------------------------------------


def run_one_chip(args) -> dict:
    run_child("device-ops", args, timeout_s=600)
    first, _, took1 = boot_engine(args, "engine-boot1")
    log1 = first.log_text()
    first.stop()
    say(f"boot 1: healthy in {took1:.1f}s (warm-up: {warmup_seconds(log1)})")
    engine, url, took2 = boot_engine(args, "engine-boot2")
    log2 = engine.log_text()
    say(f"boot 2: healthy in {took2:.1f}s (warm-up: {warmup_seconds(log2)})")
    check_cache(cache_events(log1), cache_events(log2), args.rehearse)
    check_step_programs("engine-boot2", args.rehearse)
    device = serve_phase(args, engine, url, with_chain=True)
    engine.stop()
    return device


def run_four_chips(args) -> dict:
    run_child("tp-logits", args, timeout_s=900)
    engine, url, took = boot_engine(args, "engine-tp")
    say(f"TP boot: healthy in {took:.1f}s (warm-up: "
        f"{warmup_seconds(engine.log_text())})")
    check_step_programs("engine-tp", args.rehearse)
    device = serve_phase(args, engine, url, with_chain=False)
    check(device["count"] == args.chips,
          f"the engine server sees {device['count']} devices, not "
          f"{args.chips}")
    engine.stop()
    return device


def parent(args) -> int:
    os.makedirs(OUT, exist_ok=True)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        device = run_one_chip(args) if args.chips == 1 \
            else run_four_chips(args)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        stop_all()
        shutil.rmtree(IR_DIR, ignore_errors=True)
    say(f"passed in {time.monotonic() - T0:.1f}s"
        + (" (REHEARSAL on the CPU: not a chip run)" if args.rehearse
           else ""))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# -- children: these, and only these, import JAX -----------------------------


def _require_device(args):
    import jax

    from generativeaiexamples_tpu.utils.platform import setup_compile_cache

    devs = jax.devices()
    print(f"[child] {len(devs)} x {devs[0].device_kind} "
          f"(platform {devs[0].platform}), cache {setup_compile_cache()}",
          flush=True)
    if args.rehearse:
        assert devs[0].platform == "cpu", devs
    else:
        assert devs[0].platform == "tpu", (
            f"needs a TPU, JAX found {devs[0].platform!r}")
    assert len(devs) == args.chips, (len(devs), args.chips)
    return devs


def _timed(fn, n: int = 5):
    """(result, median seconds) of a jitted call, after one warm call."""
    import jax

    out = jax.block_until_ready(fn())
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return out, sorted(ts)[len(ts) // 2]


def child_device_ops(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    _require_device(args)
    from generativeaiexamples_tpu.ops.attention import (
        flash_attention, mha_reference)
    from generativeaiexamples_tpu.ops.encoder_attention import (
        encoder_attention)
    from generativeaiexamples_tpu.serving.engine_model import _tree_layout
    from generativeaiexamples_tpu.serving.paged_attention import (
        paged_attention, paged_attention_dispatch,
        paged_attention_reference, paged_tree_attention_int8_reference_fused,
        paged_tree_attention_reference)
    from generativeaiexamples_tpu.serving.paged_attention_int8 import (
        paged_attention_int8, paged_attention_int8_reference_fused,
        quantize_kv)
    from generativeaiexamples_tpu.serving.paged_attention_tree import (
        paged_tree_attention)

    interpret = args.rehearse
    if args.rehearse:  # interpret mode on the CPU: f32, the tests' bound
        B, H, KH, Hd, ps, maxp, dt, tol = (3, 4, 2, 128, 128, 2,
                                           jnp.float32, 2e-5)
        fB, fS, eB, eH, eS, eD = 1, 128, 2, 4, 64, 16
    else:  # llama3-8b / arctic-embed-l widths in the served dtype; the
        # bound is a few bf16 roundings (2^-8) of an O(1) output
        B, H, KH, Hd, ps, maxp, dt, tol = (64, 32, 8, 128, 128, 4,
                                           jnp.bfloat16, 2e-2)
        fB, fS, eB, eH, eS, eD = 2, 2048, 32, 16, 512, 64
    R, TREE = 4, (3, 4)
    r = 1 + TREE[0] * TREE[1]
    P = B * maxp + 2  # page 0 the sink, the last one the int8 POISON
    rng = np.random.default_rng(args.seed)
    keys = iter(jax.random.split(jax.random.key(args.seed), 16))

    def rand(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dt)

    k_pages, v_pages = rand(KH, P, ps, Hd), rand(KH, P, ps, Hd)
    kq, ks = (x.at[:, P - 1].set(bad) for x, bad in zip(
        quantize_kv(k_pages.astype(jnp.float32)), (127, jnp.nan)))
    vq, vs = (x.at[:, P - 1].set(bad) for x, bad in zip(
        quantize_kv(v_pages.astype(jnp.float32)), (127, jnp.nan)))
    # fused int8 pool [2, L, KH, P, ps, Hd] + scales; the kernels index
    # the layer inside their DMA descriptors: attend layer 1 of 2
    kv = jnp.stack([jnp.stack([kq, vq]), jnp.stack([kq, vq])], axis=1)
    sc = jnp.stack([jnp.stack([ks, vs]), jnp.stack([ks, vs])], axis=1)
    layer = 1
    # ragged: one token, a page and one, the whole table, then random.
    # A table entry past the last page the longest form's span reaches
    # is DEAD: the int8 kernels get the poison page there (a dead page
    # copied or multiplied is a NaN), the references the sink.
    lengths = rng.integers(1, maxp * ps - r, (B,))
    lengths[:3] = 1, ps + 1, maxp * ps - r + 1
    dead = np.arange(maxp) >= -(-(lengths[:, None] + r - 1) // ps)
    own = rng.permutation(np.arange(1, P - 1)).reshape(B, maxp)
    table = jnp.asarray(np.where(dead, 0, own), jnp.int32)
    poisoned = jnp.asarray(np.where(dead, P - 1, own), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    q1, qR, qT = rand(B, H, Hd), rand(B, R, H, Hd), rand(B, H, r, Hd)
    _, anc = _tree_layout(*TREE)

    def ref(fn, *a, **kw):  # the XLA reference at full f32 precision
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(fn, static_argnames=tuple(kw))(
                *[x.astype(jnp.float32) if x.dtype == dt else x for x in a],
                **kw), np.float32)

    failures = []

    def compare(name, fn, arrays, want):
        # arrays are ARGUMENTS of the jitted call: closed over, a pool
        # would be baked into the executable as a 100 MB constant
        jitted = jax.jit(fn)
        got, secs = _timed(lambda: jitted(*arrays))
        got = np.asarray(got, np.float32)
        assert got.shape == want.shape, (name, got.shape, want.shape)
        err = float(np.max(np.abs(got - want)))
        ok = np.isfinite(got).all() and np.allclose(got, want, atol=tol,
                                                    rtol=tol)
        print(f"[device-ops] {name}: max|kernel - reference| {err:.3e} "
              f"(atol = rtol = {tol:g}), {secs * 1e3:.3f} ms "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        if not ok:
            failures.append(name)

    pool8 = (kv, sc, poisoned, lengths)
    layer8 = (kv[:, layer], sc[:, layer], table, lengths)
    pool16 = (k_pages, v_pages, table, lengths)
    compare("int8 paged decode",
            lambda q, *pool: paged_attention_int8(q, *pool, layer,
                                                  interpret=interpret),
            (q1, *pool8),
            ref(paged_attention_int8_reference_fused, q1, *layer8))
    compare("int8 paged verify (q_rep=4)",
            lambda q, *pool: paged_attention_int8(
                q, *pool, layer, q_rep=R, interpret=interpret),
            (qR, *pool8),
            np.stack([ref(paged_attention_int8_reference_fused, qR[:, j],
                          *layer8[:3], lengths + j)
                      for j in range(R)], axis=1))
    compare("int8 paged tree (3,4)",
            lambda q, *pool: paged_attention_int8(
                q.transpose(0, 2, 1, 3), *pool, layer, q_rep=r, tree=TREE,
                interpret=interpret).transpose(0, 2, 1, 3),
            (qT, *pool8),
            ref(lambda *a: paged_tree_attention_int8_reference_fused(
                *a, anc), qT, *layer8))
    # the decode step's append: the kernel's pool against the scatters'
    # (QuantPagePool.append's two forms) must hold the same BYTES
    from jax.experimental.pallas import tpu as pltpu

    from generativeaiexamples_tpu.serving.kv_cache import (
        QuantPagePool, token_slots)

    k_new, v_new = rand(KH, B, Hd), rand(KH, B, Hd)

    def appended(use_pallas, kv, sc, k_new, v_new):
        slots = token_slots(KH, table[:, 0], lengths % ps, use_pallas)
        pool = QuantPagePool(kv, sc, ps).append(layer, slots, k_new, v_new)
        return pool.kv, pool.s

    appended = jax.jit(appended, static_argnums=0)
    if interpret:
        pltpu.set_tpu_interpret_mode()
    got, secs = _timed(lambda: appended(True, kv, sc, k_new, v_new))
    pltpu.set_tpu_interpret_mode(None)
    want = appended(False, kv, sc, k_new, v_new)
    ok = all(bool(jnp.array_equal(g, w, equal_nan=True))  # the poison's
             for g, w in zip(got, want)) \
        and not bool(jnp.array_equal(got[0], kv))
    print(f"[device-ops] int8 K/V append (kernel vs scatters): the same "
          f"bytes, {secs * 1e3:.3f} ms {'ok' if ok else 'FAILED'}", flush=True)
    if not ok:
        failures.append("int8 K/V append")
    # latent attention's absorbed paged kernel and the held experts'
    # grouped int8 matmul (A.X-K1's widths on the chip), each against
    # its XLA form
    from generativeaiexamples_tpu.ops import moe
    from generativeaiexamples_tpu.ops.quant import QuantizedTensor
    from generativeaiexamples_tpu.serving.paged_attention_mla import (
        paged_attention_mla, paged_attention_mla_reference)

    mH, mC, mW = (4, 128, 256) if interpret else (64, 512, 640)
    mla_pool = rand(2, P, ps, mW).at[..., mW - 64:].set(0)
    mla_q = (rand(B, mH, mW) * 0.1).at[..., mW - 64:].set(0)
    mla = dict(latent=mC, scale=mW ** -0.5)
    compare("latent paged decode (absorbed)",
            lambda q, pool, t, ln: paged_attention_mla(
                q, pool, layer, t, ln, interpret=interpret, **mla),
            (mla_q, mla_pool, table, lengths),
            ref(lambda q, pool, t, ln: paged_attention_mla_reference(
                q, pool, layer, t, ln, **mla), mla_q, mla_pool, table,
                lengths))
    gE, gK, gN, gT, gk = (4, 128, 256, 16, 4) if interpret \
        else (12, 2048, 7168, 128, 8)
    local = jnp.asarray(rng.integers(0, 16 * gE, (gT, gk)), jnp.int32)
    plan = moe.dispatch_plan(jnp.minimum(local, gE), gE)  # 1 in 16 held
    gx = rand(plan.rows.shape[0], gK)
    gw = QuantizedTensor(
        jnp.asarray(rng.integers(-127, 128, (2, gE, gK, gN)), jnp.int8),
        jnp.full((2, gE, gN), gK ** -0.5 * 3 ** 0.5 / 127, jnp.float32))
    used = np.repeat(np.arange(plan.tile_group.shape[0])
                     < int(plan.n_tiles[0]), plan.tm)[:, None]
    compare("grouped int8 expert matmul",
            lambda x, q, s, *pl_: jnp.where(used, moe.grouped_matmul_pallas(
                x, QuantizedTensor(q, s), layer,
                moe.DispatchPlan(None, None, *pl_, None, plan.tm),
                interpret=interpret), 0),
            (gx, gw.q, gw.s, plan.tile_group, plan.n_tiles),
            np.where(used, ref(lambda x, q, s, *pl_: moe.grouped_matmul_reference(
                x, QuantizedTensor(q, s), layer,
                moe.DispatchPlan(None, None, *pl_, None, plan.tm)),
                gx, gw.q, gw.s, plan.tile_group, plan.n_tiles), 0))
    # a state-space layer's in-place state update (granite-4.0-h-small's
    # widths on the chip) against its XLA form: live slots, idle ones
    from generativeaiexamples_tpu.serving import ssm_state_update as ssm

    sB, sH, sP, sN = (3, 8, 16, 128) if interpret else (16, 128, 64, 128)
    def s_rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    s_state = s_rand(2, sB, sH, sP, sN)
    s_live = jnp.asarray(rng.integers(0, 2, (sB,)) > 0).at[0].set(True)
    s_step = jnp.asarray(rng.uniform(0.001, 0.1, (sB, sH)), jnp.float32)
    s_x, s_b, s_c = (s_rand(sB, sH, sP).astype(dt), s_rand(sB, sN).astype(dt),
                     s_rand(sB, sN).astype(dt))
    s_args = (s_state, s_live, s_step, -4.0 * s_step, s_x, s_b, s_c)

    def ssm_form(on):
        def run(state, *a):  # (state, y) -> one array to compare
            state, y = ssm.ssm_state_update(state, layer, *a, use_pallas=on)
            return jnp.concatenate([state[layer].reshape(-1), y.reshape(-1)])
        return run

    if interpret:
        real = ssm.ssm_state_update_pallas
        ssm.ssm_state_update_pallas = lambda *a: real(*a, interpret=True)
    compare("state-space state update (in place)", ssm_form(True), s_args,
            ref(ssm_form(False), *s_args))
    if interpret:
        ssm.ssm_state_update_pallas = real
    want = ref(paged_attention_reference, q1, *pool16)
    compare("bf16 paged decode (in-repo kernel)",
            lambda *a: paged_attention(*a, interpret=interpret),
            (q1, *pool16), want)
    if not interpret:  # the route the dispatcher serves at Hd % 128 == 0
        compare("bf16 paged decode (dispatch)",
                lambda *a: paged_attention_dispatch(*a, use_pallas=True),
                (q1, *pool16), want)
    compare("bf16 paged tree (3,4)",
            lambda *a: paged_tree_attention(*a, TREE, interpret=interpret),
            (qT, *pool16),
            ref(lambda *a: paged_tree_attention_reference(*a, anc), qT,
                *pool16))
    fq, fk, fv = (rand(fB, H, fS, Hd), rand(fB, KH, fS, Hd),
                  rand(fB, KH, fS, Hd))
    flen = jnp.asarray([fS - 5 * i for i in range(fB)], jnp.int32)
    compare(f"flash prefill (S={fS})",
            lambda q, k, v, ln: flash_attention(
                q, k, v, causal=True, lengths=ln, interpret=interpret),
            (fq, fk, fv, flen),
            ref(lambda q, k, v, ln: mha_reference(
                q, k, v, causal=True, lengths=ln), fq, fk, fv, flen))
    eq, ek, ev = (rand(eB, eH, eS, eD), rand(eB, eH, eS, eD),
                  rand(eB, eH, eS, eD))
    elen = jnp.asarray(rng.integers(1, eS + 1, (eB,)), jnp.int32)
    # rows past a sequence's length are padding: compare the valid ones
    valid = (np.arange(eS)[None, :] < np.asarray(elen)[:, None])[
        :, None, :, None]
    want = ref(lambda q, k, v, ln: mha_reference(
        q, k, v, causal=False, lengths=ln), eq, ek, ev, elen)
    compare("encoder attention (grouped heads)",
            lambda q, k, v, ln: jnp.where(valid, encoder_attention(
                q, k, v, ln, interpret=interpret), 0),
            (eq, ek, ev, elen), np.where(valid, want, 0))
    assert not failures, f"kernels off their reference: {failures}"

    # -- retrieval: what replaces the reference's GPU index -----------------
    from generativeaiexamples_tpu.ops.ivf import IVFIndex
    from generativeaiexamples_tpu.ops.topk import mips_topk

    N, D, Q, K, nlist, nprobe = ((4000, 64, 16, 4, 8, 2) if args.rehearse
                                 else (200_000, 1024, 64, 4, 64, 16))
    t0 = time.perf_counter()
    corpus = rng.standard_normal((N, D), dtype=np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    queries = rng.standard_normal((Q, D), dtype=np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    exact = np.argpartition(-(queries @ corpus.T), K, axis=1)[:, :K]
    print(f"[device-ops] corpus {N} x {D}, {Q} queries, exact numpy top-{K} "
          f"in {time.perf_counter() - t0:.1f}s", flush=True)

    def recall(ids):
        ids = np.asarray(ids)
        return float(np.mean([len(set(ids[i]) & set(exact[i])) / K
                              for i in range(Q)]))

    db = jnp.asarray(corpus)
    (_, ids), secs = _timed(lambda: mips_topk(jnp.asarray(queries), db, K))
    flat = recall(ids)
    print(f"[device-ops] flat MIPS top-{K}: recall@{K} {flat:.4f}, "
          f"{secs * 1e3:.2f} ms for {Q} queries", flush=True)
    assert flat >= 0.99, flat
    del db
    t0 = time.perf_counter()
    index = IVFIndex(corpus, nlist, nprobe=nprobe, seed=args.seed)
    print(f"[device-ops] IVF trained: nlist {index.nlist}, longest list "
          f"{index.max_list_len}, {time.perf_counter() - t0:.1f}s", flush=True)
    for probes, gated in ((index.nlist, True), (nprobe, False)):
        t0 = time.perf_counter()
        # one query per call: the refine gathers nprobe partitions/query
        ids = np.stack([np.asarray(index.search(queries[i:i + 1], K,
                                                nprobe=probes)[1][0])
                        for i in range(Q)])
        got = recall(ids)
        print(f"[device-ops] IVF nprobe {probes}/{index.nlist}: recall@{K} "
              f"{got:.4f}{'' if gated else ' (printed, not gated)'}, "
              f"{(time.perf_counter() - t0) / Q * 1e3:.2f} ms/query incl. "
              f"compile", flush=True)
        assert not gated or got >= 0.99, got
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[device-ops] device memory peak "
          f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
          f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB", flush=True)


def child_tp_logits(args) -> None:
    """The same prefill on the TP mesh and on one chip; last-position
    logits must agree within bf16 tolerance (random weights make greedy
    tokens a coin-flip under another reduction order: compare logits)."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    devs = _require_device(args)
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.parallel.mesh import build_mesh
    from generativeaiexamples_tpu.serving import engine_model
    from generativeaiexamples_tpu.serving import sharding as shd
    from generativeaiexamples_tpu.serving.kv_cache import PagePool

    cfg = (llama.LlamaConfig.tiny() if args.rehearse
           else llama.LlamaConfig.llama3_8b())
    S, ps = 128, 128
    tokens = jnp.asarray(np.random.default_rng(args.seed).integers(
        0, 256, (1, S)), jnp.int32)
    length = jnp.asarray(S - 7, jnp.int32)
    table_row = jnp.asarray([1], jnp.int32)

    def last_logits(params, mesh):
        sharding = (NamedSharding(mesh, shd.KV_POOL_SPEC)
                    if mesh is not None else None)
        pool = PagePool.zeros(cfg, 3, ps, dtype=cfg.dtype, sharding=sharding)
        logits, _ = engine_model.prefill_step(
            params, cfg, pool, tokens, length, table_row, None, mesh=mesh)
        return np.asarray(logits, np.float32)

    mesh = shd.compatible_mesh(cfg, build_mesh())
    print(f"[tp-logits] mesh {dict(mesh.shape)}", flush=True)
    t0 = time.perf_counter()
    params = shd.init_sharded_params(cfg, mesh, args.seed, quantize=True)
    jax.block_until_ready(params)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs]
    print(f"[tp-logits] TP={mesh.shape['tensor']} params in "
          f"{time.perf_counter() - t0:.1f}s; bytes_in_use per chip "
          f"{[round(b / 2**30, 2) for b in in_use]} GiB", flush=True)
    if not args.rehearse:  # leaves must be BORN sharded
        assert in_use[0] <= 2 * (sum(in_use) / len(in_use)), in_use
    tp = last_logits(params, mesh)
    del params
    gc.collect()
    params = llama.init_params_on_device(cfg, args.seed, quantize=True)
    one = last_logits(params, None)
    err = float(np.max(np.abs(tp - one)))
    scale = float(np.max(np.abs(one)))
    print(f"[tp-logits] last-position logits [{one.shape[0]}]: max|TP - one "
          f"chip| {err:.4f}, max|logit| {scale:.3f}, argmax "
          f"{int(tp.argmax())} vs {int(one.argmax())}", flush=True)
    assert np.isfinite(tp).all() and np.isfinite(one).all()
    assert err <= 5e-2 * max(scale, 1.0), (err, scale)


CHILDREN = {"device-ops": child_device_ops, "tp-logits": child_tp_logits}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the tensor-parallel path and nothing else")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and of the data")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on the CPU backend; proves the "
                         "script's control flow, never the chip")
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        CHILDREN[args.child](args)
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
