"""The system under test, built the way `serving/__main__.py` builds it:
seeded int8 weights on the device, `LLMEngine`, the encoders where the
configuration has them, `OpenAIServer` in front and, for a chain cell,
the chain server as a CPU-only child with remote connectors. The sizes
come from the cell's configuration file, not from `--model-size`.
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


def model_dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's view of the published config.json keys."""
    heads = int(config["num_attention_heads"])
    return {"n_layers": int(config["num_hidden_layers"]), "n_heads": heads,
            "n_kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim")
                            or config["hidden_size"] // heads),
            "rope_theta": float(config["rope_theta"]),
            "rms_eps": float(config["rms_norm_eps"]),
            "tie_embeddings": bool(config.get("tie_word_embeddings", False))}


def llama_config(config: Dict[str, Any]):
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models.llama import LlamaConfig

    d = model_dims(config)
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]), dim=int(config["hidden_size"]),
        n_layers=d["n_layers"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], head_dim=d["head_dim"],
        mlp_dim=int(config["intermediate_size"]),
        rope_theta=d["rope_theta"], rms_eps=d["rms_eps"],
        max_seq_len=int(config["max_position_embeddings"]),
        tie_embeddings=d["tie_embeddings"],
        dtype=jnp.dtype(config["serving"].get("dtype", "bfloat16")))


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
    lcfg: Any = None
    tokenizer: Any = None
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)


def build(config: Dict[str, Any], seed: int, devices) -> Built:
    """Weights from the seed on the device(s), then the engine(s). The
    llama path is the path every decoder of this repo shares."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness.bench_tokenizer import WordTokenizer
    from generativeaiexamples_tpu.models import bert, llama
    from generativeaiexamples_tpu.serving import sharding as shd
    from generativeaiexamples_tpu.serving.engine import LLMEngine

    b = Built()
    s = config["serving"]
    b.lcfg = lcfg = llama_config(config)
    ecfg = engine_config(config)
    quantize = ecfg.quantize_weights == "int8"
    t0 = time.monotonic()
    if len(devices) > 1:
        from generativeaiexamples_tpu.parallel.mesh import build_mesh

        b.mesh = shd.compatible_mesh(lcfg, build_mesh(devices=devices))
        b.params = shd.init_sharded_params(lcfg, b.mesh, seed,
                                           quantize=quantize)
    else:
        b.params = llama.init_params_on_device(lcfg, seed, quantize=quantize)
    jax.block_until_ready(b.params)
    b.tokenizer = WordTokenizer(lcfg.vocab_size)
    b.llm = LLMEngine(b.params, lcfg, b.tokenizer, ecfg,
                      n_pages=s.get("n_pages"), mesh=b.mesh)
    enc = config.get("encoders")
    if enc:
        def encoder(spec, engine_cls, key):
            bcfg = dataclasses.replace(
                getattr(bert.BertConfig, spec["geometry"])(),
                dtype=jnp.dtype(spec.get("dtype", "bfloat16")),
                **spec.get("overrides", {}))
            return engine_cls(
                bert.init_params(bcfg, jax.random.PRNGKey(key)), bcfg,
                WordTokenizer(bcfg.vocab_size), **spec.get("engine", {}))

        from generativeaiexamples_tpu.serving.encoders import (
            EmbeddingEngine, RerankEngine)

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
