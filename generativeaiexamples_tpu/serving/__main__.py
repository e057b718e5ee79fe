"""Engine server launcher: `python -m generativeaiexamples_tpu.serving`.

Replaces the NIM/NeMo-Retriever container entrypoints. Configured via
the AppConfig tree (APP_* env / --config file):

  engine.weights_path   HF snapshot dir (empty => random-init tiny model,
                        the hermetic/dev mode — no weights, no network)
  llm.model_name        served model id
  engine.quantize_weights  "int8" to quantize at load

Serves /v1/chat/completions, /v1/completions, /v1/embeddings,
/v1/ranking, /health, /metrics on one port.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import time

import jax
import jax.numpy as jnp


def build_engines(cfg, model_size: str = "tiny", seed: int = 0):
    from generativeaiexamples_tpu.models import bert, llama
    from generativeaiexamples_tpu.parallel.mesh import (
        build_mesh, maybe_initialize_distributed)
    from generativeaiexamples_tpu.serving import sharding as shd
    from generativeaiexamples_tpu.serving.encoders import (
        EmbeddingEngine, RerankEngine)
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import load_tokenizer

    # Router-only fleet process (fleet.replicas=0 + replica_urls): no
    # local engine at all — each replica is its own engine-server
    # process on its own host/slice (the mesh/DCN data-parallel axis
    # as processes; each may be TP internally), this process places
    # requests by prefix locality and proxies the SSE streams.
    urls = (cfg.fleet.replica_urls or "").strip()
    if cfg.engine.multihost and (cfg.fleet.replicas > 1 or urls):
        raise ValueError(
            "engine.multihost=true serves ONE engine spanning all hosts "
            "behind rank 0; it cannot combine with a replica fleet "
            f"(fleet.replicas={cfg.fleet.replicas}, replica_urls="
            f"{urls!r}). Run fleets as separate single-slice processes, "
            "or drop fleet config for multi-host.")
    if urls and cfg.fleet.replicas <= 0:
        from generativeaiexamples_tpu.serving.fleet import build_fleet

        tokenizer = (load_tokenizer(cfg.engine.weights_path)
                     if cfg.engine.weights_path else load_tokenizer("byte"))
        fleet = build_fleet(cfg, engines=None, tokenizer=tokenizer).start()
        logging.info("router-only fleet over %s", urls)
        return fleet, None, None

    maybe_initialize_distributed(cfg.mesh)
    if jax.process_count() > 1 and not cfg.engine.multihost:
        raise ValueError(
            f"jax.distributed spans {jax.process_count()} processes but "
            "engine.multihost=false — the engine would fail at its first "
            "cross-process host fetch. Set engine.multihost=true (and see "
            "serving/multihost.py for the supported profile), or launch "
            "without a coordinator for single-host serving.")
    # Multi-chip: build the mesh from config (default MeshConfig puts all
    # devices on the tensor axis — TP serving, the NIM INFERENCE_GPU_COUNT
    # replacement; multi-host keeps TP on ICI and spans hosts via the
    # dcn_* axes) and shard params + KV pool over it.
    mesh = build_mesh(cfg.mesh) if len(jax.devices()) > 1 else None

    if cfg.engine.weights_path:
        from generativeaiexamples_tpu.models.hf_loader import (
            llama_config_from_hf, load_llama)

        lcfg = llama_config_from_hf(cfg.engine.weights_path)
        if mesh is not None:
            mesh = shd.compatible_mesh(lcfg, mesh)
        params, lcfg = load_llama(
            cfg.engine.weights_path, cfg=lcfg, mesh=mesh,
            quantize=cfg.engine.quantize_weights == "int8")
        tokenizer = load_tokenizer(cfg.engine.weights_path)
    else:
        geometry = {
            "tiny": llama.LlamaConfig.tiny,
            "1b": llama.LlamaConfig.llama3_2_1b,
            "8b": llama.LlamaConfig.llama3_8b,
            "70b": llama.LlamaConfig.llama3_70b,
        }[model_size]
        lcfg = geometry()
        logging.warning("engine.weights_path empty: seeded random %s model "
                        "(dev/bench mode, seed %d)", model_size, seed)
        # Drawn on device leaf by leaf in the final dtype and layout:
        # llama3-8b int8 never exists as f32/bf16, and under a mesh no
        # leaf is ever whole on one chip.
        quantize = cfg.engine.quantize_weights == "int8"
        if mesh is not None:
            mesh = shd.compatible_mesh(lcfg, mesh)
            params = shd.init_sharded_params(lcfg, mesh, seed,
                                             quantize=quantize)
        else:
            params = llama.init_params_on_device(lcfg, seed,
                                                 quantize=quantize)
        tokenizer = load_tokenizer("byte")
    if mesh is not None:
        logging.info("llama params sharded over mesh %s", dict(mesh.shape))

    n_replicas = max(1, cfg.fleet.replicas)
    if n_replicas > 1 or urls:
        # Data-parallel fleet: N engines share the (read-only) params
        # but own their page pools, prefix caches and scheduler
        # threads; the prefix-locality router fronts them behind the
        # same engine-shaped surface, so the OpenAI server below is
        # unchanged. Remote replicas from fleet.replica_urls join the
        # same router.
        from generativeaiexamples_tpu.serving.fleet import build_fleet

        engines = [LLMEngine(params, lcfg, tokenizer, cfg.engine, mesh=mesh)
                   for _ in range(n_replicas)]
        # Autoscaler spawn lane: new replicas share the (read-only)
        # params and the module-level jitted steps, so a spawn costs
        # engine state only, not a recompile.
        llm = build_fleet(
            cfg, engines=engines, tokenizer=tokenizer,
            engine_factory=lambda: LLMEngine(params, lcfg, tokenizer,
                                             cfg.engine, mesh=mesh))
    else:
        llm = LLMEngine(params, lcfg, tokenizer, cfg.engine, mesh=mesh)
    warm = os.environ.get("ENGINE_WARMUP", "1") != "0"
    if warm:
        # Precompile prefill/decode variants so the first multi-request
        # burst never stalls live streams behind a compile; the
        # persistent compile cache makes later boots cheap. Sampled
        # variants warm too — temperature>0 is the API default, so the
        # first real request must not eat the compile. (Fleet: the
        # jitted steps are module-level, so replica 2..N reuse replica
        # 1's compilations.)
        t0 = time.perf_counter()
        llm.warmup(sampled=True,
                   long_prompts=os.environ.get("ENGINE_WARMUP_LONG",
                                               "0") == "1")
        logging.info("engine warm-up done in %.1fs",
                     time.perf_counter() - t0)
    if cfg.engine.multihost and jax.process_index() != 0:
        # Follower ranks replay rank 0's dispatch records (the
        # multihost.run_follower loop, driven from main()) — their
        # scheduler threads never start and encoders never build; rank 0
        # alone fronts the OpenAI surface. Warmup DID run above: cross-
        # process collectives pair by launch order, so every rank must
        # enter the same warmup programs in the same sequence, and
        # ENGINE_WARMUP must therefore match across ranks.
        return llm, None, None
    llm.start()

    hermetic = not cfg.engine.weights_path
    # Encoders: real weights come from their OWN snapshots + tokenizers
    # (a llama tokenizer against a BERT vocab would silently index out of
    # range). Without weights: seeded random models — 32-wide toys beside
    # the tiny LLM, the published geometries (arctic-embed-l's 1024
    # dimensions are what the chain server's default config expects)
    # beside a full-size one — and disabled (None -> 503) when the LLM
    # is real.
    emb = rr = None
    if cfg.embeddings.weights_path:
        from generativeaiexamples_tpu.models.hf_loader import load_bert

        bparams, bcfg = load_bert(cfg.embeddings.weights_path)
        emb = EmbeddingEngine(bparams, bcfg,
                              load_tokenizer(cfg.embeddings.weights_path))
    elif hermetic:
        bcfg = (bert.BertConfig.tiny(vocab_size=512) if model_size == "tiny"
                else dataclasses.replace(bert.BertConfig.arctic_embed_l(),
                                         dtype=jnp.bfloat16))
        emb = EmbeddingEngine(
            bert.init_params(bcfg, jax.random.PRNGKey(seed + 1)),
            bcfg, tokenizer)
    if cfg.reranker.weights_path:
        from generativeaiexamples_tpu.models.hf_loader import load_bert

        rparams, rcfg = load_bert(cfg.reranker.weights_path, n_labels=1)
        rr = RerankEngine(rparams, rcfg,
                          load_tokenizer(cfg.reranker.weights_path))
    elif hermetic:
        rcfg = (bert.BertConfig(vocab_size=512, dim=32, n_layers=2,
                                n_heads=2, mlp_dim=64, max_position=64,
                                n_labels=1) if model_size == "tiny"
                else dataclasses.replace(bert.BertConfig.reranker_base(),
                                         dtype=jnp.bfloat16))
        rr = RerankEngine(
            bert.init_params(rcfg, jax.random.PRNGKey(seed + 2)),
            rcfg, tokenizer)
    if warm:
        t0 = time.perf_counter()
        for enc in (emb, rr):
            if enc is not None:
                enc.warmup()
        logging.info("encoder warm-up done in %.1fs",
                     time.perf_counter() - t0)
    return llm, emb, rr


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--config", default=None, help="YAML/JSON config file")
    ap.add_argument("--model-size", default="tiny",
                    choices=("tiny", "1b", "8b", "70b"),
                    help="geometry when engine.weights_path is empty")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights when "
                         "engine.weights_path is empty")
    ap.add_argument("--coordinator", default="",
                    help="rank-0 address host:port for jax.distributed "
                         "(multi-host serving; overrides "
                         "mesh.coordinator_address)")
    ap.add_argument("--num-processes", type=int, default=0,
                    help="total jax.distributed processes "
                         "(overrides mesh.num_processes)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this host's rank, 0..num_processes-1 "
                         "(overrides mesh.process_id)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()
    logging.basicConfig(level=logging.DEBUG if args.verbose else logging.INFO)

    from generativeaiexamples_tpu.config.wizard import load_config
    from generativeaiexamples_tpu.serving.openai_server import (
        OpenAIServer, run_server)
    from generativeaiexamples_tpu.utils.platform import setup_compile_cache

    logging.info("persistent compile cache: %s", setup_compile_cache())
    cfg = load_config(args.config)
    if args.coordinator or args.num_processes or args.process_id is not None:
        cfg = dataclasses.replace(cfg, mesh=dataclasses.replace(
            cfg.mesh,
            coordinator_address=(args.coordinator
                                 or cfg.mesh.coordinator_address),
            num_processes=args.num_processes or cfg.mesh.num_processes,
            process_id=(args.process_id if args.process_id is not None
                        else cfg.mesh.process_id)))
    # Same switch as the chain server's (tracing.enabled /
    # ENABLE_TRACING): without it the surface extracts no traceparent
    # and `engine.generate` never joins its caller's trace.
    from generativeaiexamples_tpu.obs import tracing

    tracing.setup(cfg)
    llm, emb, rr = build_engines(cfg, args.model_size, args.seed)
    if cfg.engine.multihost and jax.process_index() != 0:
        from generativeaiexamples_tpu.serving.multihost import run_follower

        logging.info("rank %d/%d: follower replay loop (rank 0 serves "
                     "the OpenAI surface)", jax.process_index(),
                     jax.process_count())
        try:
            run_follower(llm)
        finally:
            llm.stop()
        return
    server = OpenAIServer(llm, emb, rr, model_name=cfg.llm.model_name,
                          embed_model_name=cfg.embeddings.model_name,
                          serving_cfg=cfg.serving)
    logging.info("engine server on %s:%d (backend=%s)", args.host, args.port,
                 jax.default_backend())
    run_server(server, args.host, args.port)


if __name__ == "__main__":
    main()
