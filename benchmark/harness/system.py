"""The system under test, built the way `serving/__main__.py` builds it:
seeded int8 weights on the device, `LLMEngine`, the encoders where the
configuration has them, `OpenAIServer` in front and, for a chain cell,
the chain server as a CPU-only child with remote connectors. The sizes
come from the cell's configuration file, not from `--model-size`; what
the model is (its configuration in the program's terms, its weights)
comes from the file's architecture entry, benchmark/architectures/.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import uuid
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUT_DIR = os.path.join(ROOT, ".bench_out")


def load_config(bench_dir: str, name: str) -> Dict[str, Any]:
    with open(os.path.join(bench_dir, "configs", name + ".json")) as fh:
        return json.load(fh)


def engine_config(config: Dict[str, Any]):
    from generativeaiexamples_tpu.config.schema import EngineConfig

    s = config["serving"]
    fields = dict(s.get("engine", {}))
    if "prefill_buckets" in fields:
        fields["prefill_buckets"] = tuple(fields["prefill_buckets"])
    return dataclasses.replace(
        EngineConfig(), quantize_weights=s["quantize_weights"],
        kv_dtype=s["kv_dtype"], **fields)


def require_devices(chips: int, allow_cpu: bool):
    """The devices of this run. No accelerator, or fewer chips than the
    cell asks for, is an error: a measurement never falls back."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise SystemExit(f"benchmark: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); refusing to measure")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs[:chips]


@dataclasses.dataclass
class Built:
    """The engines, and what the reference check and the readers need."""

    llm: Any = None
    emb: Any = None
    rr: Any = None
    mesh: Any = None
    params: Any = None
    tokenizer: Any = None
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)


def build(config: Dict[str, Any], seed: int, devices) -> Built:
    """Weights from the seed on the device(s), then the engine(s). What
    the model is comes from the configuration's architecture entry
    (benchmark/architectures/)."""
    import jax

    from benchmark import architectures
    from benchmark.harness.bench_tokenizer import WordTokenizer
    from generativeaiexamples_tpu.serving.engine import LLMEngine

    b = Built()
    s = config["serving"]
    arch = architectures.load(config)
    mcfg = arch.model_config(config)
    ecfg = engine_config(config)
    t0 = time.monotonic()
    b.params, b.mesh = arch.init_params(config, mcfg, seed, devices)
    jax.block_until_ready(b.params)
    b.tokenizer = WordTokenizer(int(config["vocab_size"]))
    b.llm = LLMEngine(b.params, mcfg, b.tokenizer, ecfg,
                      n_pages=s.get("n_pages"), mesh=b.mesh)
    enc = config.get("encoders")
    if enc:
        from generativeaiexamples_tpu.serving.encoders import (
            EmbeddingEngine, RerankEngine)

        def encoder(spec, engine_cls, key):
            return architectures.load_encoder(spec).build(spec, engine_cls,
                                                          key)

        b.emb = encoder(enc["embedder"], EmbeddingEngine, seed % 2**31 + 1)
        if "reranker" in enc:
            b.rr = encoder(enc["reranker"], RerankEngine, seed % 2**31 + 2)
        jax.block_until_ready((b.emb.params, b.rr.params if b.rr else None))
    b.phases["weights_s"] = time.monotonic() - t0
    return b


def warm_up(b: Built, buckets: List[int]) -> None:
    """Greedy variants only, and only the prefill buckets this cell's
    prompts reach."""
    t0 = time.monotonic()
    b.llm.warmup(buckets=buckets, sampled=False)
    for enc in (b.emb, b.rr):
        if enc is not None:
            enc.warmup()
    b.phases["warmup_s"] = time.monotonic() - t0


class ServerThread:
    """An aiohttp application on 127.0.0.1:<free port>, in a thread."""

    def __init__(self, app):
        self.app = app
        self.url: Optional[str] = None
        self._loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self._runner = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-openai-server")

    def _run(self) -> None:
        from aiohttp import web

        asyncio.set_event_loop(self._loop)
        self._runner = web.AppRunner(self.app)
        self._loop.run_until_complete(self._runner.setup())
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        self._loop.run_until_complete(site.start())
        port = self._runner.addresses[0][1]
        self.url = f"http://127.0.0.1:{port}"
        self._ready.set()
        self._loop.run_forever()
        self._loop.run_until_complete(self._runner.cleanup())

    def start(self) -> str:
        self._thread.start()
        if not self._ready.wait(60):
            raise RuntimeError("the OpenAI server did not start")
        return self.url

    def stop(self) -> None:
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(30)


def serve(b: Built, config: Dict[str, Any]) -> ServerThread:
    from generativeaiexamples_tpu.serving.openai_server import OpenAIServer

    server = OpenAIServer(b.llm, b.emb, b.rr,
                          model_name=config.get("served_model_name", "bench"))
    st = ServerThread(server.app)
    st.start()
    return st


def http_json(method: str, url: str, body=None, headers=None,
              timeout: float = 300.0):
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


def child_env(extra: Dict[str, str]) -> Dict[str, str]:
    """A child that must not see the chip and must resolve the package."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("APP_", "ENGINE_"))}
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


class ChainChild:
    """The chain server beside the engine: every connector remote."""

    def __init__(self, config: Dict[str, Any], engine_url: str):
        os.makedirs(OUT_DIR, exist_ok=True)
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{port}"
        env = child_env({k: str(v).replace("{engine_url}", engine_url)
                         for k, v in config["chain"]["env"].items()})
        self._log = open(os.path.join(OUT_DIR, "chain-server.log"), "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.harness.chain_child",
             "--port", str(port)], cwd=ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT)

    def wait_healthy(self, timeout_s: float = 120.0) -> None:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if self.proc.poll() is not None:
                raise RuntimeError(f"chain server exited with code "
                                   f"{self.proc.returncode}; see "
                                   f"{self._log.name}")
            try:
                if http_json("GET", self.url + "/health", timeout=5)[0] == 200:
                    return
            except OSError:
                pass
            time.sleep(0.5)
        raise RuntimeError("chain server not healthy in time")

    def ingest(self, files) -> int:
        """Upload the seeded corpus through /documents."""
        n = 0
        for name, text in files:
            boundary = uuid.uuid4().hex
            body = (f"--{boundary}\r\nContent-Disposition: form-data; "
                    f'name="file"; filename="{name}"\r\n'
                    f"Content-Type: text/plain\r\n\r\n").encode() \
                + text.encode() + f"\r\n--{boundary}--\r\n".encode()
            status, raw = http_json(
                "POST", self.url + "/documents", body, timeout=600,
                headers={"Content-Type":
                         f"multipart/form-data; boundary={boundary}"})
            if status != 200:
                raise RuntimeError(f"/documents -> {status}: {raw[:300]!r}")
            n += 1
        return n

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(20)
        self._log.close()


class LoadGen:
    """The load generator child (benchmark/harness/loadgen.py)."""

    def __init__(self, base_url: str, endpoint: str, model: str,
                 schedule: Dict[str, Any]):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.harness.loadgen",
             "--base-url", base_url, "--endpoint", endpoint,
             "--model", model],
            cwd=ROOT, env=child_env({}), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        self.proc.stdin.write(json.dumps(schedule) + "\n")
        self.proc.stdin.flush()
        if self.proc.stdout.readline().strip() != "ready":
            self.stop()
            raise RuntimeError("the load generator did not come up")

    def go(self, t_open: float) -> None:
        self.proc.stdin.write(f"go {t_open!r}\n")
        self.proc.stdin.flush()

    def result(self, timeout_s: float) -> List[Dict[str, Any]]:
        try:
            out, _ = self.proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.stop()
            raise RuntimeError("the load generator did not finish in time")
        if self.proc.returncode != 0:
            raise RuntimeError(f"load generator exited with code "
                               f"{self.proc.returncode}")
        return json.loads(out.strip().splitlines()[-1])["records"]

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(20)
