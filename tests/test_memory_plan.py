"""Memory-budget planner arithmetic pinned against REAL allocations.

The planner's exact lines (weights, pool page bytes) must equal the
bytes the CPU backend actually allocates per device — f32 and int8
weight trees, f32 and fused-int8 KV pools — and the fail-fast path must
carry the full breakdown plus the smallest mesh that would fit.
"""

import math

import jax
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig, MeshConfig
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.parallel.mesh import build_mesh
from generativeaiexamples_tpu.serving import memory_plan as mp
from generativeaiexamples_tpu.serving import sharding as shd

TINY = llama.LlamaConfig.tiny()


def _per_device_bytes(tree, dev) -> int:
    total = 0
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            if sh.device == dev:
                total += sh.data.nbytes
    return total


def _sharded_params(mesh, quantize: bool):
    from generativeaiexamples_tpu.ops.quant import quantize_llama_params

    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    if quantize:
        params = quantize_llama_params(params)
    return shd.shard_llama_params(params, TINY, mesh)


@pytest.mark.parametrize("quantize", [False, True],
                         ids=["f32", "int8"])
@pytest.mark.parametrize("mcfg", [
    MeshConfig(ici_tensor=2, ici_data=-1),
    MeshConfig(ici_tensor=2, ici_fsdp=2, ici_data=-1),
], ids=["tp2", "tp2_fsdp2"])
def test_weight_bytes_match_allocation(eight_devices, mcfg, quantize):
    mesh = build_mesh(mcfg)
    params = _sharded_params(mesh, quantize)
    dev = jax.devices()[0]
    measured = _per_device_bytes(params, dev)
    predicted = mp.weight_bytes_per_device(
        TINY, mp.mesh_axis_sizes(mesh), quantize=quantize)
    assert predicted == measured


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_pool_page_bytes_match_allocation(eight_devices, kv_dtype):
    from generativeaiexamples_tpu.serving.kv_cache import PagePool
    from jax.sharding import NamedSharding

    mesh = build_mesh(MeshConfig(ici_tensor=2, ici_data=-1))
    ecfg = EngineConfig(page_size=8, kv_dtype=kv_dtype)
    n_pages = 7
    if kv_dtype == "int8":
        pool = PagePool.zeros(
            TINY, n_pages, ecfg.page_size, dtype="int8",
            sharding=NamedSharding(mesh, shd.KV_FUSED_SPEC),
            scale_sharding=NamedSharding(mesh, shd.KV_FUSED_SCALE_SPEC))
    else:
        pool = PagePool.zeros(
            TINY, n_pages, ecfg.page_size, dtype=TINY.dtype,
            sharding=NamedSharding(mesh, shd.KV_POOL_SPEC))
    dev = jax.devices()[0]
    measured = _per_device_bytes(pool, dev)
    predicted = mp.pool_page_bytes_per_device(
        TINY, ecfg, mp.mesh_axis_sizes(mesh))
    assert predicted * n_pages == measured


def test_engine_pool_sized_from_plan(eight_devices):
    """auto_pool_pages: the engine's real pool == plan.pool_pages, the
    plan's exact lines == allocated bytes, and the planner's TOTAL
    (exact + estimates) lands within 10% of what it claims measured
    against real weight+pool allocations plus its own scratch lines."""
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    mesh = build_mesh(MeshConfig(ici_tensor=2, ici_data=-1))
    params = _sharded_params(mesh, quantize=False)
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                        prefill_buckets=(16, 32),
                        pace_emission_max_streams=0,
                        auto_pool_pages=True)
    eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg, mesh=mesh,
                    use_pallas=False)
    plan = eng.memory_plan
    assert plan is not None
    assert eng.pool.n_pages == plan.pool_pages > 0
    dev = jax.devices()[0]
    alloc = (_per_device_bytes(params, dev)
             + _per_device_bytes(eng.pool, dev))
    exact = sum(l.bytes_per_device for l in plan.lines if l.exact)
    assert exact + plan.pool_bytes_per_device == alloc
    # The 10% acceptance bound: planner total vs measured-plus-scratch.
    predicted = plan.total_bytes_per_device
    measured = alloc + sum(l.bytes_per_device
                           for l in plan.lines if not l.exact)
    assert abs(predicted - measured) / measured < 0.10
    # Gauges: headroom surfaced, multihost 0 (single process).
    snap = eng.metrics.snapshot()
    assert snap["planner_headroom_bytes"] == plan.headroom_bytes > 0
    assert snap["multihost_processes"] == 0
    eng.stop()


def test_default_sizing_unchanged_without_knob(eight_devices):
    """auto_pool_pages=false (the default) must keep the legacy pool
    arithmetic byte-for-byte: no plan, gauge at 0."""
    from generativeaiexamples_tpu.serving.engine import LLMEngine
    from generativeaiexamples_tpu.utils.tokenizer import ByteTokenizer

    mesh = build_mesh(MeshConfig(ici_tensor=2, ici_data=-1))
    params = _sharded_params(mesh, quantize=False)
    ecfg = EngineConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                        prefill_buckets=(16, 32),
                        pace_emission_max_streams=0)
    eng = LLMEngine(params, TINY, ByteTokenizer(), ecfg, mesh=mesh,
                    use_pallas=False)
    assert eng.memory_plan is None
    max_pages = ecfg.max_seq_len // ecfg.page_size
    assert eng.pool.n_pages == ecfg.max_batch_size * max_pages + 1
    assert eng.metrics.snapshot()["planner_headroom_bytes"] == 0
    eng.stop()


def test_fail_fast_breakdown_and_hint():
    """A 70B plan on one 16 GiB device must raise with the per-line
    breakdown AND the smallest mesh that would fit."""
    lcfg = llama.LlamaConfig.llama3_70b()
    ecfg = EngineConfig(quantize_weights="int8", kv_dtype="int8",
                        auto_pool_pages=True)
    with pytest.raises(mp.MemoryPlanError) as ei:
        mp.plan_engine_memory(lcfg, ecfg, axis_sizes={"tensor": 1},
                              hbm_bytes_per_device=16 << 30)
    msg = str(ei.value)
    for needle in ("memory plan does not fit", "weights", "kv_pool",
                   "headroom", "smallest mesh that fits: ici_tensor="):
        assert needle in msg, f"missing {needle!r} in:\n{msg}"
    plan = ei.value.plan
    assert plan is not None and plan.fit_pages < (
        ecfg.max_seq_len // ecfg.page_size) + 1
    # The hinted geometry must itself plan cleanly.
    hinted = mp.smallest_fitting_mesh(lcfg, ecfg, 16 << 30)
    assert hinted is not None
    mp.plan_engine_memory(lcfg, ecfg, axis_sizes=hinted,
                          hbm_bytes_per_device=16 << 30)


def test_70b_example_config_plans_cleanly():
    """The shipped 70B multi-host example config builds its memory plan
    (the acceptance shape: fits at the named geometry, or would fail
    fast with the breakdown)."""
    import os

    from generativeaiexamples_tpu.config.wizard import load_config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_config(
        os.path.join(repo, "configs", "llama3_70b_multihost.yaml"),
        env={})
    assert cfg.engine.multihost and cfg.engine.auto_pool_pages
    assert cfg.engine.quantize_weights == "int8"
    plan = mp.plan_engine_memory(
        llama.LlamaConfig.llama3_70b(), cfg.engine,
        axis_sizes={"tensor": cfg.mesh.ici_tensor},
        n_processes=2, devices_per_host=cfg.mesh.ici_tensor // 2)
    assert plan.pool_pages >= (cfg.engine.max_seq_len
                               // cfg.engine.page_size) + 1
    assert "2 host(s)" in plan.breakdown()


def test_dryrun_needs_no_devices():
    """70B geometry planning is pure arithmetic — exact weight line and
    per-host scaling work from axis sizes alone."""
    lcfg = llama.LlamaConfig.llama3_70b()
    ecfg = EngineConfig(quantize_weights="int8", kv_dtype="int8",
                        hbm_gb_per_device=95.0, auto_pool_pages=True)
    plan = mp.plan_engine_memory(lcfg, ecfg, axis_sizes={"tensor": 8},
                                 n_processes=2, devices_per_host=4)
    w = plan.lines[0]
    assert w.name == "weights" and w.exact
    # 70B int8: ~1 byte/param + f32 scales, split 8 ways.
    assert 8.0 * mp.GiB < w.bytes_per_device < 9.0 * mp.GiB
    assert plan.per_host(w.bytes_per_device) == 4 * w.bytes_per_device
    assert plan.pool_pages >= (ecfg.max_seq_len // ecfg.page_size) + 1
    assert "2 host(s)" in plan.breakdown()
