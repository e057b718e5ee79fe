# %% [markdown]
# # 02 — The TPU serving engine
#
# What the reference outsources to NIM/TRT-LLM, driven directly:
# continuous batching, paged KV cache, device-side sampling. Runs on
# the CPU backend with a tiny model so it executes anywhere; the same
# code serves Mistral-7B int8 on a v5e (`benchmark/README.md`).

# %%
import os
import sys

# __file__ is undefined inside a Jupyter kernel; fall back to cwd.
_here = (os.path.dirname(os.path.abspath(__file__))
         if "__file__" in globals() else os.getcwd())
sys.path.insert(0, os.path.abspath(os.path.join(_here, "..", "..")))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.serving.engine import LLMEngine
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

# %% [markdown]
# ## Build and warm an engine
# `warmup()` precompiles every (bucket, group-size) prefill variant and
# the decode K-buckets, so live traffic never stalls behind XLA.

# %%
cfg = llama.LlamaConfig.tiny()
params = llama.init_params(cfg, jax.random.PRNGKey(0))
ecfg = EngineConfig(max_batch_size=4, max_seq_len=128, page_size=16,
                    prefill_buckets=(32,), decode_steps_per_dispatch=4)
engine = LLMEngine(params, cfg, ByteTokenizer(), ecfg, use_pallas=False)
engine.warmup()
engine.start()

# %% [markdown]
# ## Stream tokens
# `generate_stream` yields per-token events — the same stream the
# OpenAI-compatible server re-emits as SSE.

# %%
for ev in engine.generate_stream([10, 11, 12, 13], max_new_tokens=6):
    print(ev["token_id"], end=" ")
print()

# %% [markdown]
# ## Long prompts: chunked prefill
# Prompts beyond the largest prefill bucket run bucket-size chunks into
# a scratch cache and scatter into pages once — up to the full page
# capacity of the sequence.

# %%
long_prompt = [(i * 3) % cfg.vocab_size for i in range(70)]  # > bucket 32
out = [ev["token_id"] for ev in
       engine.generate_stream(long_prompt, max_new_tokens=4)
       if ev["token_id"] >= 0]
print("long-prompt continuation:", out)

# %%
print("metrics:", engine.metrics.snapshot())
engine.stop()

# %% [markdown]
# ## Speculative decoding
# `speculative_k > 0` turns on greedy self-speculation: an on-device
# n-gram drafter proposes k tokens from the sequence's own history and
# ONE verify forward checks them — up to k+1 committed tokens per
# weight read. Output is exactly the greedy continuation (acceptance
# only changes speed); sampled requests serve through a per-request
# non-speculative fallback plan (they just don't speculate), and
# `speculative_tree_branches` widens the draft into a multi-branch
# tree verified in one step (see docs/architecture.md).

# %%
import dataclasses

spec_engine = LLMEngine(params, cfg, ByteTokenizer(),
                        dataclasses.replace(ecfg, speculative_k=2),
                        use_pallas=False).start()
prompt = [7, 8, 9]
spec_out = [ev["token_id"] for ev in
            spec_engine.generate_stream(prompt, max_new_tokens=12)
            if ev["token_id"] >= 0]
snap = spec_engine.metrics.snapshot()
print("speculative tokens:", spec_out)
print("committed tokens per verify step:",
      round(snap.get("spec_tokens_per_step", 1.0), 2))
spec_engine.stop()

# Equality guarantee: same tokens as the plain greedy engine. (This
# comparison is deterministic within one environment; across XLA
# versions a random-weight near-tie could legitimately flip — see
# docs/ENGINEERING_NOTES.md "honesty notes". If this assert ever
# fails after a toolchain bump, check logit gaps before suspecting
# the engine.)
plain = LLMEngine(params, cfg, ByteTokenizer(), ecfg,
                  use_pallas=False).start()
plain_out = [ev["token_id"] for ev in
             plain.generate_stream(prompt, max_new_tokens=12)
             if ev["token_id"] >= 0]
plain.stop()
assert spec_out == plain_out, (spec_out, plain_out)
print("speculative == greedy ✓")

# %% [markdown]
# ## Multi-chip
# Under a `jax.sharding.Mesh` the same engine runs tensor-parallel:
# `serving.sharding.shard_llama_params` + `LLMEngine(..., mesh=mesh)`.
# See `tests/test_serving_tp.py` and `__graft_entry__.dryrun_multichip`.
