# %% [markdown]
# # 03 — Fine-tuning: SFT, LoRA, retriever customization
#
# The reference ships fine-tuning as NeMo notebooks (models/Gemma etc.);
# here every recipe is a sharded train step on the same mesh machinery
# as serving. Tiny geometries keep this runnable on CPU.

# %%
import os
import sys

# __file__ is undefined inside a Jupyter kernel; fall back to cwd.
_here = (os.path.dirname(os.path.abspath(__file__))
         if "__file__" in globals() else os.getcwd())
sys.path.insert(0, os.path.abspath(os.path.join(_here, "..", "..")))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import optax

from generativeaiexamples_tpu.models import bert, llama
from generativeaiexamples_tpu.training import lora as lora_lib
from generativeaiexamples_tpu.training import retriever_ft as rft
from generativeaiexamples_tpu.training import trainer
from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

# %% [markdown]
# ## Full SFT step

# %%
cfg = llama.LlamaConfig.tiny()
params = llama.init_params(cfg, jax.random.PRNGKey(0))
tcfg = trainer.TrainConfig(learning_rate=1e-3, warmup_steps=2)
opt = trainer.make_optimizer(tcfg)
step = jax.jit(trainer.make_train_step(cfg, tcfg, opt))
# On a real slice: trainer.shard_train_state(params, cfg, opt, mesh)
# places params/optimizer with the TP/FSDP specs before stepping.
opt_state = opt.init(params)
batch = trainer.synthetic_batch(cfg, batch=4, seq=16)
params, opt_state, metrics = step(params, opt_state, batch)
print("sft loss:", float(metrics["loss"]))

# %% [markdown]
# ## LoRA: adapter-only training, merge for serving

# %%
lcfg = lora_lib.LoraConfig(rank=4, targets=("wq", "wv"))
adapters = lora_lib.init_lora(cfg, lcfg, jax.random.PRNGKey(1))
lopt = optax.adam(1e-2)
lstep = jax.jit(lora_lib.make_lora_train_step(cfg, lcfg, lopt))
lopt_state = lopt.init(adapters)
for _ in range(3):
    adapters, lopt_state, m = lstep(adapters, lopt_state, params, batch)
print("lora loss:", float(m["loss"]))
served_params = lora_lib.merge(params, adapters, lcfg)  # LoRA-free serving

# %% [markdown]
# ## Retriever customization (contrastive InfoNCE)

# %%
bcfg = bert.BertConfig.tiny(vocab_size=256)
bparams = bert.init_params(bcfg, jax.random.PRNGKey(2))
pairs = [("what chips serve llama", "llama serves on tpu v5e chips"),
         ("how big is the memory", "sixteen gigabytes of hbm per chip"),
         ("what links the chips", "ici links connect chips in a slice"),
         ("what compiles kernels", "pallas compiles custom tpu kernels")]
tuned = rft.finetune(bparams, bcfg, ByteTokenizer(), pairs, epochs=3,
                     batch_size=4,
                     ft=rft.RetrieverFTConfig(learning_rate=1e-3))
print("retriever fine-tune done")
