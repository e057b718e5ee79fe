"""Pipeline parallelism (parallel/pipeline.py): the GPipe schedule must
be a pure re-scheduling of the non-pipelined computation — same loss,
same gradients — and compose with tensor/data axes on the same mesh.
Closes VERDICT r2 weak #5 (`dcn_pipeline` knob with no implementation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig, MeshConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.parallel import pipeline as pp
from generativeaiexamples_tpu.parallel.mesh import build_mesh
from generativeaiexamples_tpu.training import trainer

TINY = llama.LlamaConfig.tiny()


@pytest.fixture(scope="module")
def pp_mesh(eight_devices):
    # pipeline=2 x data=2 x tensor=2: PP composing with DP and TP.
    return build_mesh(
        MeshConfig(dcn_pipeline=2, ici_data=2, ici_tensor=-1),
        devices=jax.devices()[:8])


class TestPipelineLoss:
    def test_matches_unpipelined_loss_and_grads(self, pp_mesh):
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        batch = trainer.synthetic_batch(TINY, batch=8, seq=16)

        want_loss, want_grads = jax.value_and_grad(trainer.loss_fn)(
            params, TINY, batch["tokens"], batch["targets"], batch["mask"])

        sparams, _, _ = pp.shard_pp_train_state(
            params, TINY, trainer.make_optimizer(trainer.TrainConfig()),
            pp_mesh)
        with jax.set_mesh(pp_mesh):
            got_loss, got_grads = jax.jit(jax.value_and_grad(
                lambda p, t, y, m: pp.pipeline_loss(
                    p, TINY, t, y, m, mesh=pp_mesh, n_micro=4)))(
                sparams, batch["tokens"], batch["targets"], batch["mask"])

        np.testing.assert_allclose(float(got_loss), float(want_loss),
                                   rtol=2e-5)
        flat_w = jax.tree.leaves(want_grads)
        flat_g = jax.tree.leaves(got_grads)
        for w, g in zip(flat_w, flat_g):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=5e-4, rtol=5e-3)

    def test_single_stage_mesh_falls_through(self, eight_devices):
        mesh = build_mesh(MeshConfig(ici_tensor=-1), devices=jax.devices()[:4])
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        batch = trainer.synthetic_batch(TINY, batch=4, seq=8)
        want = trainer.loss_fn(params, TINY, batch["tokens"],
                               batch["targets"], batch["mask"])
        got = pp.pipeline_loss(params, TINY, batch["tokens"],
                               batch["targets"], batch["mask"],
                               mesh=mesh, n_micro=2)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    def test_bad_microbatch_split_rejected(self, pp_mesh):
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        batch = trainer.synthetic_batch(TINY, batch=6, seq=8)
        with pytest.raises(ValueError, match="not divisible by n_micro"):
            pp.pipeline_loss(params, TINY, batch["tokens"],
                             batch["targets"], batch["mask"],
                             mesh=pp_mesh, n_micro=4)

    def test_bad_stage_split_rejected(self, eight_devices):
        mesh = build_mesh(MeshConfig(dcn_pipeline=4, ici_data=2,
                                     ici_tensor=1),
                          devices=jax.devices()[:8])
        cfg3 = llama.LlamaConfig(vocab_size=64, dim=32, n_layers=3,
                                 n_heads=2, n_kv_heads=2, head_dim=16,
                                 mlp_dim=64, max_seq_len=64,
                                 dtype=jnp.float32)
        params = llama.init_params(cfg3, jax.random.PRNGKey(0))
        batch = trainer.synthetic_batch(cfg3, batch=4, seq=8)
        with pytest.raises(ValueError, match="not divisible by\n?.*stages"):
            pp.pipeline_loss(params, cfg3, batch["tokens"],
                             batch["targets"], batch["mask"],
                             mesh=mesh, n_micro=2)


class TestPipelineTrainStep:
    def test_full_step_updates_params(self, pp_mesh):
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        tcfg = trainer.TrainConfig(learning_rate=1e-3, warmup_steps=1,
                                   remat=False)
        opt = trainer.make_optimizer(tcfg)
        sparams, sopt, _ = pp.shard_pp_train_state(params, TINY, opt, pp_mesh)
        step = jax.jit(pp.make_pp_train_step(TINY, tcfg, opt, mesh=pp_mesh,
                                             n_micro=2))
        batch = trainer.synthetic_batch(TINY, batch=4, seq=8)
        with jax.set_mesh(pp_mesh):
            # Two steps: the warmup schedule's lr is 0 at step 0, so
            # params only move on the second update.
            new_params, sopt, metrics = step(sparams, sopt, batch)
            new_params, sopt, metrics = step(new_params, sopt, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["grad_norm"]) > 0
        before = np.asarray(jax.tree.leaves(sparams)[2])
        after = np.asarray(jax.tree.leaves(new_params)[2])
        assert not np.allclose(before, after)


class TestServingRejectsPipeline:
    def test_engine_rejects_pipeline_mesh(self, pp_mesh):
        from generativeaiexamples_tpu.serving.engine import LLMEngine
        from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

        cfg = llama.LlamaConfig(vocab_size=256, dim=64, n_layers=2,
                                n_heads=8, n_kv_heads=2, head_dim=16,
                                mlp_dim=128, max_seq_len=128,
                                dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="pipeline"):
            LLMEngine(params, cfg, ByteTokenizer(),
                      EngineConfig(max_batch_size=2, max_seq_len=64,
                                   page_size=32),
                      mesh=pp_mesh)
