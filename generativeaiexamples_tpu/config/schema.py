"""Config schema: one frozen-dataclass tree for the whole framework.

Capability parity with the reference's config system
(RetrievalAugmentedGeneration/common/configuration.py:20-258 — sections
vector_store / llm / text_splitter / embeddings / retriever / prompts),
extended with TPU-native sections the reference delegates to external
engines: `mesh` (device-mesh / parallelism layout) and `engine`
(serving-engine knobs: KV paging, batching, dtypes).

Every field can be overridden by an environment variable named
``APP_<SECTION>_<FIELD>`` (e.g. ``APP_LLM_MODELNAME``,
``APP_VECTORSTORE_URL``) — same contract as the reference
(configuration_wizard.py:45,138) so existing deploy env files translate.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class VectorStoreConfig:
    """Vector store selection and index tuning.

    Parity: configuration.py:20-47 (name/url/nlist/nprobe). The TPU build
    adds the in-process stores ("memory", "tpu", "native") that replace the
    reference's Milvus-GPU dependency (docker-compose-vectordb.yaml:57).
    """

    name: str = "memory"  # memory | tpu | native | milvus | pgvector
    url: str = ""
    nlist: int = 64  # IVF cells (native/milvus backends)
    nprobe: int = 16  # IVF cells probed at search
    # flat = exact brute-force MIPS (byte-identical to the pre-IVF
    # store); ivf = TPU-native clustered ANN (ops/ivf.py): k-means
    # centroids trained on device, searches refine only the top-nprobe
    # of nlist partitions. Honored by the in-process tpu/native store.
    index_type: str = "flat"  # flat | ivf
    # Store IVF rows as int8 + per-row scales (1/4 the f32 HBM
    # footprint; ~1e-2 relative score error). ivf only.
    quantize_int8: bool = False
    # Tiered demand-paged IVF (ops/tiered.py): HBM holds centroids +
    # the most-probed partitions' row blocks inside hbm_budget_mb, the
    # rest of the corpus lives in a host-RAM warm cache (ram_budget_mb)
    # over an mmap'd disk spill file, and a background pager promotes/
    # demotes whole partitions by probe-frequency EMA. Probes that miss
    # HBM refine on the host in the same logical search — slower, never
    # wrong. Requires index_type=ivf; single-device (no mesh). Off by
    # default — off is byte-identical to the PR-2 IVF path.
    tiered: bool = False
    # Device budget for the hot partition table (centroids excluded;
    # floored at one partition slot).
    hbm_budget_mb: int = 256
    # Host-RAM budget for the warm cache of spill-file partition blocks.
    ram_budget_mb: int = 1024
    # Directory for the tiered index's spill file. Empty = a `tiered/`
    # subdirectory of persist_dir, or a fresh temp directory when the
    # store is ephemeral.
    spill_dir: str = ""
    # Per-search decay of the pager's probe-frequency EMA (closer to 1
    # = longer memory, slower residency shifts).
    pager_ema_decay: float = 0.98
    # Durable store directory ("ingested data persists across sessions",
    # reference CHANGELOG.md:63). Empty = ephemeral; deployments set it
    # (deploy/compose.env APP_VECTORSTORE_PERSISTDIR).
    persist_dir: str = ""


@dataclass(frozen=True)
class LLMConfig:
    """Which LLM backend the chains talk to.

    Parity: configuration.py llm section (server_url/model_name/model_engine/
    model_name_pandas_ai). model_engine selects the connector:
    "tpu" = in-process JAX serving engine (the default; replaces NIM),
    "openai" = any OpenAI-compatible remote, "echo" = hermetic test fake.
    """

    server_url: str = ""
    model_name: str = "llama3-8b-instruct"
    model_engine: str = "tpu"
    model_name_pandas_ai: str = ""


@dataclass(frozen=True)
class TextSplitterConfig:
    """Token-aware splitter settings (parity: configuration.py:92-101)."""

    model_name: str = "intfloat/e5-large-v2"
    chunk_size: int = 510
    chunk_overlap: int = 200


@dataclass(frozen=True)
class EmbeddingConfig:
    """Embedder selection (parity: configuration.py embeddings section)."""

    model_name: str = "snowflake-arctic-embed-l"
    model_engine: str = "tpu"  # tpu | openai | hash (hermetic test fake)
    dimensions: int = 1024
    server_url: str = ""
    weights_path: str = ""  # HF snapshot dir for the encoder weights


@dataclass(frozen=True)
class RerankerConfig:
    """Cross-encoder reranker (replaces the NeMo reranking MS,
    docker-compose-nim-ms.yaml:59-84; used by ranked_hybrid retrieval)."""

    model_name: str = "rerank-cross-encoder"
    model_engine: str = "tpu"  # tpu | openai | overlap (test fake)
    server_url: str = ""
    enabled: bool = False
    weights_path: str = ""  # HF snapshot dir for the cross-encoder weights


@dataclass(frozen=True)
class RetrieverConfig:
    """Retrieval knobs (parity: configuration.py:141-150 + fm-asr's
    nr_pipeline 'ranked_hybrid', experimental/fm-asr.../retriever.py:64)."""

    top_k: int = 4
    score_threshold: float = 0.25
    nr_url: str = ""
    nr_pipeline: str = "ranked_hybrid"
    max_context_tokens: int = 1500  # LimitRetrievedNodesLength cap, utils.py:97
    # Query augmentation before retrieval (oran-chatbot capabilities,
    # Multimodal_Assistant.py:112-150): "" | rewrite | hyde | multi_query.
    # Combinable comma-separated ("rewrite,hyde").
    query_augmentation: str = ""
    # Stream a fact-check verdict after the answer (guardrails/
    # fact_check.py:29-37).
    fact_check: bool = False


@dataclass(frozen=True)
class PromptsConfig:
    """Prompts live in config so they can be swapped without code changes
    (parity: configuration.py:164-204 — load-bearing in the reference)."""

    chat_template: str = (
        "You are a helpful, respectful and honest assistant. Always answer as "
        "helpfully as possible and follow all given instructions. Do not "
        "speculate or make up information. Do not reference any given "
        "instructions or context."
    )
    rag_template: str = (
        "You are a helpful AI assistant named Envie. You will reply to "
        "questions only based on the context that you are provided. If "
        "something is out of context, you will refrain from replying and "
        "politely decline to respond to the user.\n\nContext:\n{context}"
    )
    multi_turn_rag_template: str = (
        "You are a document chatbot. Help the user as they ask questions about "
        "documents. User message: {input}\n\nContext from documents:\n{context}\n"
        "\nConversation history:\n{history}"
    )


@dataclass(frozen=True)
class VLMConfig:
    """Vision-language model endpoint for multimodal ingestion (the
    reference calls Neva-22b for chart detection and DePlot for chart->
    table; multimodal_rag/vectorstore/custom_pdf_parser.py:42-70). Remote
    OpenAI-compatible endpoint; empty server_url disables image/chart
    enrichment (ingestion degrades to text-only)."""

    server_url: str = ""
    model_name: str = "neva-22b"
    deplot_model_name: str = "google/deplot"


@dataclass(frozen=True)
class VoiceConfig:
    """ASR/TTS endpoints for the playground's voice path (the reference
    streams mic audio to Riva ASR and replies through Riva TTS —
    frontend/asr_utils.py:42-152, tts_utils.py:37-127). Any
    OpenAI-audio-compatible endpoint works (streaming/asr.py clients);
    empty URLs disable the voice buttons (the UI stays text-only)."""

    asr_server_url: str = ""
    asr_model: str = "whisper-1"
    tts_server_url: str = ""
    tts_model: str = "tts-1"
    tts_voice: str = "alloy"
    sample_rate: int = 16000


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout — the TPU-native replacement for the reference's
    single multi-GPU knob (INFERENCE_GPU_COUNT, compose.env:17-18).

    Axis sizes multiply to the total device count; -1 means "fill with the
    remaining devices". ici_* axes map to in-slice ICI links, dcn_data to
    cross-host DCN data parallelism (jax.distributed multi-host pods).
    """

    ici_data: int = 1  # in-slice data parallel replicas
    ici_fsdp: int = 1  # weight-sharded data parallel
    ici_tensor: int = -1  # tensor (model) parallel — default: all devices
    ici_sequence: int = 1  # sequence/context parallel (ring attention)
    ici_expert: int = 1  # expert parallel (MoE models)
    dcn_data: int = 1  # cross-host data parallel
    dcn_pipeline: int = 1  # cross-host pipeline parallel
    # Axis names are fixed by parallel.mesh.MESH_AXIS_NAMES (pipeline, data,
    # fsdp, expert, sequence, tensor) — not configurable.
    # Multi-process bring-up (jax.distributed). Empty/defaults = single
    # process (byte-identical to the pre-multihost engine). When
    # coordinator_address is set, every process must pass the same value
    # plus its own process_id in [0, num_processes); the env vars
    # JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID (or
    # the --coordinator/--num-processes/--process-id serve flags)
    # override these fields.
    coordinator_address: str = ""  # "host:port" of process 0
    num_processes: int = 0  # 0 = single process / let JAX infer
    process_id: int = -1  # -1 = single process / let JAX infer


@dataclass(frozen=True)
class EngineConfig:
    """JAX serving-engine knobs — replaces everything NIM/TRT-LLM configured
    internally (docker-compose-nim-ms.yaml:2-22)."""

    weights_path: str = ""  # HF snapshot dir or orbax checkpoint
    dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"  # bfloat16 | int8 (narrow per-token scales)
    quantize_weights: str = "none"  # none | int8
    max_batch_size: int = 8
    max_seq_len: int = 8192
    page_size: int = 128  # KV-cache page (tokens per page)
    prefill_buckets: Tuple[int, ...] = (128, 512, 1024, 2048, 4096)
    # Largest number of admissions batched into ONE prefill dispatch.
    # Caps prefill's transient activation/KV memory (a full-batch burst
    # at max_batch_size=256 would otherwise spike ~2x the steady-state
    # footprint); 0 = uncapped (group = max_batch_size).
    max_prefill_group: int = 64
    # The CEILING of a decode block, in steps: what a block runs while
    # nobody can be waiting for it (every slot live, nothing queued).
    # While an arrival can be waiting the scheduler shortens the block
    # to a warm K that fits a time budget, by the step time it observes
    # (serving/decode_block.py: BLOCK_BUDGET_MS = 60 ms, choose_k),
    # because an arrival waits about two blocks and a block is steps x
    # step time: 60 is what an arrival can afford, so only a model whose
    # step is 7.5 ms or less keeps eight steps while a slot is empty.
    # Warm-up compiles K = 1, 2 and this value rounded down to a power
    # of two; the window pool is sized with this value.
    decode_steps_per_dispatch: int = 8
    # Decode dispatch pipeline depth: blocks enqueued ahead of the host
    # fetch so device compute overlaps result readback.
    # 1 = synchronous (old behavior).
    pipeline_depth: int = 2
    # Greedy self-speculative decoding: draft k tokens per step from an
    # on-device n-gram history lookup and verify them in ONE forward —
    # up to k+1 tokens per weight read (the NIM/TRT-LLM speculative-
    # decoding role). 0 = off. Verification is greedy-only; sampled
    # requests (temperature > 0) fall back per-request to the
    # non-speculative plan on the same engine (they serve, they just
    # don't speculate). Greedy streams are always exactly the greedy
    # continuation regardless of acceptance.
    speculative_k: int = 0
    # Multi-branch tree drafts (the EAGLE/Medusa tree-verify role,
    # drafted from the n-gram history lattice): each verify step
    # proposes `speculative_tree_branches` independent k-deep
    # continuations — one per recent occurrence of the current token,
    # with the last branch following the longest-suffix (bigram) match
    # instead — and verifies the whole packed tree in ONE widened
    # decode step via a tree-attention mask. Commit semantics are identical to the
    # linear chain (accepted-prefix + bonus, byte-identical greedy
    # streams); more branches only raise the acceptance ceiling.
    # 0 or 1 = the linear single-chain draft (byte-identical to the
    # pre-tree engine). Requires speculative_k > 0.
    speculative_tree_branches: int = 0
    # Composable step plans: describe every device dispatch as a
    # declarative StepPlan {decode block, optional spec-verify width,
    # optional prefill-rider width} lowered by engine_model.plan_step,
    # so speculation and the fused prefill rider COMPOSE instead of
    # excluding each other (one warmed jitted step can carry decode +
    # tree verify + a prefill chunk). warmup() precompiles the
    # reachable plan lattice; dispatch falls back to a narrower plan
    # (drop the rider) rather than compiling a cold shape mid-traffic.
    # Off by default — off is byte-identical to the lane-exclusive
    # scheduler (speculative engines then never fuse).
    step_plans: bool = False
    # Emission pacing: a landed K-step decode block delivers up to K
    # tokens per stream at once; with few live streams the pacer
    # re-spaces those bursts over the observed block interval (capped
    # at 100 ms/token, flushed the moment a terminal event or the next
    # block arrives — completion latency is never delayed). Engaged
    # only while the number of live decode streams is <= this value;
    # bulk workloads (e.g. the B=128 throughput bench) run above it
    # and pay zero pacing overhead. 0 disables pacing entirely.
    pace_emission_max_streams: int = 16
    # Long-prompt (chunked) prefill priority lane: up to this many
    # chunks dispatch per LANDED decode block while other streams are
    # decoding (1 = the r4 behavior that put 8k-under-load TTFT at
    # 3.4 s). Idle engines always run chunks at full dispatch speed.
    prefill_chunks_per_block: int = 2
    # While a chunked prefill is in progress AND live streams are
    # decoding, cap decode blocks at this many fused steps: short
    # blocks keep the device queue shallow so prefill chunks interleave
    # at a fine grain (8k-under-load TTFT ~2 s instead of 3.4 s) while
    # the pacer keeps live-stream cadence smooth. 0 = no cap.
    prefill_decode_k_cap: int = 2
    # Fused prefill+decode dispatch (the Sarathi-Serve chunked-fusion
    # role): while live streams are decoding, an in-progress chunked
    # prefill's next chunk rides INSIDE the decode dispatch — one
    # jitted step computes the decode block AND up to
    # fused_token_budget prompt tokens against the prefill's scratch
    # cache, so long prompts advance without standalone batch-of-1
    # chunk dispatches serializing ahead of decode blocks on the
    # device queue. Falls back to the interleaved lane when the engine
    # is idle, the engine is speculative, or the fused variant isn't
    # warmed. Off by default — off is byte-identical to the
    # interleaved-lane engine.
    fused_prefill: bool = False
    # Per-fused-step prompt-token budget for the rider (bounds how much
    # a decode block's latency inflates while a prefill is fused into
    # it). The rider's chunk width is the largest power of two <=
    # min(budget, largest prefill bucket).
    fused_token_budget: int = 512
    # Fused first-token sampling: the chunk that COMPLETES a prompt
    # (chunked long prefills, prefix-cache-hit suffixes) samples its
    # first token and scatters it into the device token buffer INSIDE
    # the same dispatch (engine_model.prefill_chunk_sample_step), and
    # every other finish folds sample_token + set_last_token into one
    # program (sample_token_into) — the beat gap between a finished
    # prefill and its first decode block loses 1-2 host-side
    # dispatches. Decode-block sampling is always fused (it has lived
    # inside decode_multi_step since PR 4); this knob covers the
    # finish tails. On by default: the fused tail computes exactly the
    # unfused math with the same key stream — greedy streams bitwise-
    # identical and sampled draws key-identical on CPU CI (tests pin
    # both; on TPU the fused and unfused variants are distinct XLA
    # programs, so an argmax near-tie could in principle round
    # differently — the same program-identity caveat the fused
    # prefill rider carries). Off restores the two-dispatch finish
    # for A/B measurement.
    fused_sampling: bool = True
    # Cross-request prefix KV reuse (the RadixAttention / vLLM-APC /
    # NIM KV-reuse role, serving/prefix_cache.py): a host-side radix
    # tree maps page-granular prompt prefixes to ref-counted pool
    # pages; admissions adopt the longest cached prefix and prefill
    # ONLY the uncached suffix. Off by default — cache-off behavior is
    # identical to the pre-cache engine.
    prefix_cache: bool = False
    # Fraction of the page pool the radix tree may hold as cached
    # pages (LRU-trimmed beyond this; allocator pressure evicts
    # further — live sequences always win over the cache).
    prefix_cache_capacity: float = 0.5
    # Session KV pager (serving/kv_pager.py; requires prefix_cache):
    # tier prefix-cache pages HBM -> budgeted host RAM -> mmap'd disk
    # spill, with the radix tree as the pager's index. Eviction then
    # DEMOTES cold sessions' KV instead of destroying it (allocator
    # pressure parks a paused conversation at ~zero HBM cost) and a
    # prefix match PROMOTES non-resident pages back into the pool with
    # one batched scatter — warm-resume TTFT stays a page gather, not
    # a re-prefill, at session counts far beyond what the pool alone
    # holds. Off by default — off is byte-identical to the PR-1 cache.
    kv_pager: bool = False
    # Host-RAM budget for the warm tier, in MB (0 = no host tier:
    # demotions go straight to the disk spill). PER-HOST: under a
    # multi-host mesh each rank's host/disk tiers park only its
    # addressable shard slice of a page (kv_pager slice mode), so the
    # fleet's cold capacity scales with host count at constant
    # per-host RAM.
    kv_host_budget_mb: int = 256
    # Directory for the cold tier's spill file ("" = a per-engine temp
    # dir, removed at shutdown). The file is grown and compacted
    # crash-safely (temp + os.replace).
    kv_spill_dir: str = ""
    # SLO-aware multi-tenant QoS (serving/qos.py): requests carry a
    # priority tier (latency | standard | batch — body `priority` field
    # or x-priority header) and a tenant id (OpenAI `user` field /
    # x-tenant-id header); admission replaces the FIFO queue with
    # weighted-fair scheduling across tiers (service-per-weight, so
    # batch is throttled under latency pressure but never starved) and
    # least-served-tenant fairness within a tier, and latency-tier
    # arrivals in their TTFT phase pause lower-tier long prefills at
    # the fused-rider beat boundary (the chunk simply stops being
    # dispatched; resume is byte-identical — chunk state is snapshot-
    # based). Off by default — off is byte-identical to the FIFO
    # scheduler.
    qos: bool = False
    # Admission-bandwidth weights per tier (floored at 1 — a zero
    # weight would re-create starvation). Latency : standard : batch
    # defaults 8 : 4 : 1.
    qos_weight_latency: int = 8
    qos_weight_standard: int = 4
    qos_weight_batch: int = 1
    # With qos on, pause lower-tier in-progress long prefills while a
    # latency-tier request is in its TTFT phase (prefilling or awaiting
    # its first token) — the preemption that keeps a tenant's 8k flood
    # from sitting in front of every interactive caller.
    qos_preempt_prefill: bool = True
    # Engine flight recorder (serving/flight.py): one compact record
    # per scheduling beat (StepPlan lattice point, dispatch->ready
    # device interval vs host-side gap, busy/waiting slots per tier,
    # pager page moves) plus request lifecycle events (submit / qos
    # pick / admit / prefill chunks / first token / retire), written
    # into preallocated single-writer ring buffers and served at
    # /debug/timeline as Perfetto-loadable Chrome trace JSON
    # (scripts/analyze_timeline.py turns it into stall attribution).
    # Default ON: the append is O(1), lock-free and allocation-free —
    # overhead is pinned <= 1% by scripts/smoke_flight.py and
    # reported as a bench extra (flight_overhead_pct).
    flight_recorder: bool = True
    # Beat-ring capacity in records (the lifecycle-event ring is 4x
    # this). At one record per landed decode block, 4096 covers
    # minutes of saturated serving; older records overwrite in place.
    flight_ring_size: int = 4096
    enable_pallas_kernels: bool = True
    # Multi-host serving (jax.distributed over DCN): rank 0 runs the
    # scheduler + OpenAI surface, follower ranks replay its published
    # dispatch records (a self-describing kind + host scalars per
    # launch) so cross-process collectives pair up by launch order
    # (serving/multihost.py). Speculation, step plans, fused prefill +
    # fused sampling, the prefix cache and the kv pager all replay;
    # only batch-sharded meshes (data/fsdp > 1) are rejected at build
    # with the fetch-seam rationale. Off = byte-identical
    # single-process engine.
    multihost: bool = False
    # Size the paged-KV pool from serving/memory_plan.py instead of the
    # max_batch_size*max_pages worst case: the planner accounts sharded
    # weights + scratch + warmup transients + headroom against per-
    # device HBM and allocates every remaining byte as KV pages (or
    # fails fast with the per-host breakdown and the smallest mesh that
    # would fit). Off = legacy sizing, byte-identical.
    auto_pool_pages: bool = False
    # Per-device HBM budget in GiB for the memory planner. 0 = probe
    # the backend (TPU memory_stats; a 4 GiB default on the CPU/test
    # backend where there is no real HBM limit).
    hbm_gb_per_device: float = 0.0
    # Fraction of per-device HBM the planner refuses to allocate
    # (compiler scratch, fragmentation, XLA temporaries beyond the
    # modeled warmup transients). Exposed as planner_headroom_bytes.
    planner_headroom_fraction: float = 0.1


@dataclass(frozen=True)
class ServingConfig:
    """Chain-server request-path knobs: cross-request dynamic
    micro-batching for the RAG pre-generation stages (embed / rerank /
    ANN search — serving/batcher.py, the Triton dynamic-batcher role the
    reference delegates to NIM microservices), and the executor width
    that bounds how many requests can be in flight at once."""

    # Coalesce concurrent embed / rerank / vector-search callers into
    # one device dispatch. Off by default — off is byte-identical to
    # the serialize-per-request behavior.
    microbatch_enabled: bool = False
    # Most requests one dispatch may absorb. Keep <= the encoder
    # engines' max_batch so a coalesced group still fits one forward.
    microbatch_max_batch: int = 16
    # How long the first queued request waits for company before the
    # dispatch launches anyway. Under load the window never adds
    # latency (the device is busy; arrivals pile up behind the running
    # dispatch); idle single requests pay at most this once.
    microbatch_max_wait_us: int = 2000
    # ThreadPoolExecutor width for the chain server's blocking chain /
    # ingest / search work (and the OpenAI server's stream bridging —
    # each live SSE stream parks one thread on a blocking queue.get).
    # Must comfortably exceed microbatch_max_batch, or concurrency caps
    # below the batch window and coalescing can never fill a dispatch.
    executor_workers: int = 64
    # Edge admission control (serving/qos.py EdgeAdmission): bound the
    # requests in flight PER TIER at the OpenAI server; past the bound
    # a request is shed with 429 + Retry-After before it queues on the
    # engine — overload costs the caller one RTT, not an unbounded
    # wait. Off by default (no shedding; depth still tracked).
    qos_edge: bool = False
    # Per-tier in-flight bounds (0 = unbounded for that tier). The
    # latency bound should sit near the engine's slot count — a
    # latency request that would queue deeper than that has already
    # missed its TTFT target, so shedding it fast is the honest answer.
    qos_bound_latency: int = 32
    qos_bound_standard: int = 64
    qos_bound_batch: int = 128
    # Retry-After hint (seconds) on shed responses.
    qos_retry_after_s: float = 1.0


@dataclass(frozen=True)
class FleetConfig:
    """Serving fleet: N data-parallel engine replicas behind a
    prefix-locality router (serving/fleet.py + serving/router.py).
    Placement scores prefix-cache locality (per-replica shadow radix
    trees fed by the engines' admission/eviction reports), queue
    depth, and session affinity, with health-based eviction and
    graceful drain. Default off (replicas=1, no replica_urls): the
    single-engine server path is byte-identical to a fleet-less
    build."""

    # Local (in-process) engine replicas built by the server launcher.
    # 1 = no fleet at all. >1 emulates data parallelism in one process
    # (CPU tests/bench; multi-chip hosts give each engine a slice).
    replicas: int = 1
    # Comma-separated base URLs of REMOTE engine-server processes
    # (process-per-replica over the mesh/DCN data axis: each replica
    # runs `python -m generativeaiexamples_tpu.serving` on its own
    # host/slice; this process routes and proxies SSE). Non-empty
    # enables fleet mode even with replicas=1.
    replica_urls: str = ""
    # prefix = locality + load + affinity scoring (the default);
    # least_load and round_robin are the degraded comparison policies.
    router_policy: str = "prefix"
    # How long a session (OpenAI `user` field / x-session-id header)
    # stays pinned to the replica that served it.
    affinity_ttl_s: float = 300.0
    # Queue-depth penalty in TOKENS per queued request when scoring a
    # locality hit: a cached prefix stops winning once its replica is
    # matched_tokens/load_penalty_tokens requests deeper than the
    # shallowest one.
    load_penalty_tokens: int = 256
    # Per-replica shadow-tree budget (pages of page_size tokens).
    shadow_capacity_pages: int = 4096
    # Health-probe period for the background prober; 0 disables the
    # thread (check_health() can still be called explicitly).
    health_interval_s: float = 10.0
    # Consecutive failed probes before a replica is evicted (any
    # success resets the count): one slow poll must never kill a
    # loaded replica.
    health_fail_threshold: int = 3
    # Remote replicas' health probes get their OWN short connect/read
    # deadline (NOT the 300 s stream timeout), backed off up to 3x
    # with consecutive failures.
    probe_timeout_s: float = 2.0
    # -- disaggregated prefill/decode (serving/disagg.py, the
    # DistServe/Mooncake shape). Off by default: the static colocated
    # fleet is byte-identical with disagg=False.
    # Comma-separated roles assigned positionally to replicas (locals
    # r0..rN first, then remote h0..hM): prefill | decode | mixed.
    # "prefill" replicas run prefill stages only and NEVER receive
    # decode placements; unlisted replicas stay "mixed". E.g.
    # "prefill,decode" splits a 2-replica fleet.
    replica_roles: str = ""
    # Two-stage serving: the router plans prefill on a prefill-role
    # replica, the finished prefill's KV pages transfer to the chosen
    # decode replica (one batched gather + one scatter, int8 codes +
    # scales verbatim — bit-identical), and decode resumes from the
    # transferred prefix through the normal prefix-cache hit path
    # with zero re-prefill. Requires engine.prefix_cache on the
    # replicas and at least one prefill-role replica; any stage
    # failure falls back to colocated serving on the same stream.
    disagg: bool = False
    # Prompts shorter than this many tokens skip the two-stage plan
    # and serve directly on a decode-pool replica (still never on a
    # prefill-role one): a short prompt's prefill is cheaper than a
    # page transfer, and keeping it off the prefill pool is what
    # shields latency-tier TTFT while long prefills storm that pool.
    # 0 = every full-page prompt goes two-stage.
    disagg_min_prompt_tokens: int = 0
    # How long the fleet waits for the prefill stage to finish before
    # falling back to colocated serving.
    disagg_prefill_timeout_s: float = 120.0
    # Deadline for the export -> import page transfer itself.
    disagg_transfer_timeout_s: float = 60.0
    # Pipelined prefill-overlap transfer: completed prefill pages ship
    # to the decode replica in chunks WHILE later chunks still
    # compute, and decode admits as soon as the covered prefix lands
    # (instead of waiting for the whole prefill + one monolithic
    # transfer). Off = the serialized PR-14 plan, byte-identical.
    disagg_pipeline: bool = False
    # Device-path KV transfer: when both replicas' pools are
    # addressable from this process (in-process fleet on one host /
    # slice — mesh.devices_colocated), pages move device-to-device
    # (int8 codes + scales verbatim, no serialization, no host
    # bounce). Any device-path failure permanently falls back to the
    # GKVT host-bounce wire for that replica pair, on the same
    # stream. Off = every transfer takes the host bounce.
    disagg_device_path: bool = False
    # Transfer chunk size in PAGES for the pipelined/chunked path
    # (each chunk is one export->import window). 0 = whole-prefix
    # windows (chunking only at the pager's max_pages gather bound).
    disagg_transfer_chunk_pages: int = 0
    # -- elastic autoscaler (serving/autoscaler.py). Off by default:
    # the static fleet is byte-identical with autoscale=False.
    autoscale: bool = False
    # Admitting-replica bounds. min_replicas is the always-hot floor
    # for latency traffic; max_replicas caps spawn growth.
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 4
    # Pre-warmed, non-admitting spares kept for instant scale-up.
    autoscale_warm_pool: int = 1
    # Control-loop poll period.
    autoscale_interval_s: float = 2.0
    # Tier-weighted in-flight requests PER ACTIVE REPLICA above which
    # the loop wants to scale up / below which it wants to scale down
    # (the hysteresis band lives between the two).
    autoscale_up_depth: float = 8.0
    autoscale_down_depth: float = 1.0
    # Consecutive over/under-threshold polls required before acting —
    # an oscillating signal resets both counters (no flapping).
    autoscale_up_ticks: int = 2
    autoscale_down_ticks: int = 5
    # Minimum seconds between ANY two scale actions.
    autoscale_cooldown_s: float = 20.0
    # Allow a fully idle fleet to park its last replica (batch-tier
    # scale-to-zero); arriving demand wakes one replica instead of
    # getting a 503.
    autoscale_scale_to_zero: bool = False
    # Latency-histogram scale-up signals (ROADMAP item-5 remainder):
    # scale up when the fleet's latency-tier queue-wait p95 — or TTFT
    # p95 — over the LAST POLL WINDOW (bucket-wise histogram delta,
    # not the cumulative view) exceeds these, even while raw queue
    # depth looks healthy. 0 disables each signal (depth-only, the
    # PR-13 behavior). Role-aware under disagg: the signal is
    # attributed to the role pool whose replicas produced it, so
    # prefill and decode pools scale independently.
    autoscale_up_queue_wait_p95_ms: float = 0.0
    autoscale_up_ttft_p95_ms: float = 0.0
    # How scale-up SPAWNS new replicas once the warm pool is empty:
    # "local" builds an in-process engine (engine_factory, the PR-15
    # behavior); "process" launches a `python -m
    # generativeaiexamples_tpu.serving` subprocess per replica
    # (ROADMAP 3b — process isolation, own device footprint) and
    # joins it over HTTP once its /health answers. The child inherits
    # this process's APP_CONFIG_FILE / APP_* environment.
    autoscale_spawn: str = "local"
    # How long a process spawn may take to answer /health before the
    # subprocess is killed and the scale-up counts as failed.
    autoscale_spawn_ready_timeout_s: float = 120.0
    # -- chaos harness (serving/chaos.py). Off by default; on, the
    # fleet carries an armed ChaosMonkey (live chaos_injected_*
    # counters, a "chaos" /debug/timeline lane) for fault drills —
    # injections themselves still only fire when a schedule runs.
    chaos: bool = False
    # Seed for the monkey's replica picks: same seed, same targets.
    chaos_seed: int = 0


@dataclass(frozen=True)
class TracingConfig:
    """OTel export settings (parity: common/tracing.py, ENABLE_TRACING)."""

    enabled: bool = False
    otlp_endpoint: str = "http://localhost:4317"
    service_name: str = "chain-server"


@dataclass(frozen=True)
class AppConfig:
    """Root of the config tree."""

    vector_store: VectorStoreConfig = field(default_factory=VectorStoreConfig)
    llm: LLMConfig = field(default_factory=LLMConfig)
    text_splitter: TextSplitterConfig = field(default_factory=TextSplitterConfig)
    embeddings: EmbeddingConfig = field(default_factory=EmbeddingConfig)
    reranker: RerankerConfig = field(default_factory=RerankerConfig)
    retriever: RetrieverConfig = field(default_factory=RetrieverConfig)
    vlm: VLMConfig = field(default_factory=VLMConfig)
    voice: VoiceConfig = field(default_factory=VoiceConfig)
    prompts: PromptsConfig = field(default_factory=PromptsConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)


def as_dict(cfg) -> dict:
    """Config tree -> plain nested dict (for logging / serialization)."""
    return dataclasses.asdict(cfg)


def replace(cfg, **kw):
    """Functional update of a frozen config node."""
    return dataclasses.replace(cfg, **kw)


# Env-var section names: APP_<SECTION>_<FIELD> where SECTION strips
# underscores ("vector_store" -> VECTORSTORE), matching the reference's
# camelCase-uppercased convention (configuration_wizard.py:49-81).
def env_section_name(field_name: str) -> str:
    return field_name.replace("_", "").upper()


def env_var_name(section: str, field_name: str) -> str:
    return f"APP_{env_section_name(section)}_{env_section_name(field_name)}"
