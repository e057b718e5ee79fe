"""The seam between models/ and serving/ (serving/served_models.py): one
record an architecture, looked up by the config's class, and no consumer
that branches on an architecture by name.

The pinned refusals and memory plans are the PARENT's (PR 46's tree),
taken by calling `_refuse_unwalked_lanes` and `plan_engine_memory` there:
tests/served_models_pins.json says how.
"""

import ast
import dataclasses
import inspect
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.config.schema import EngineConfig
from generativeaiexamples_tpu.models import (
    hybrid_ssm, latent_moe, llama, sparse_attn_moe, window_attn_moe)
from generativeaiexamples_tpu.serving import (
    engine, engine_model, fleet, memory_plan, served_hybrid, served_latent,
    served_models, served_sparse, served_window)
from generativeaiexamples_tpu.serving.engine import _refuse_unwalked_lanes
from generativeaiexamples_tpu.serving.kv_cache import PagePool
from generativeaiexamples_tpu.serving.served_models import served

PINS = json.loads(
    (pathlib.Path(__file__).parent / "served_models_pins.json").read_text())
TINY = {
    "llama": llama.LlamaConfig.tiny(),
    "looped": dataclasses.replace(llama.LlamaConfig.tiny(), n_passes=3),
    "latent": latent_moe.LatentMoeConfig.tiny(),
    "hybrid": hybrid_ssm.HybridSsmConfig.tiny(),
    "sparse": sparse_attn_moe.SparseAttnMoeConfig.tiny(),
    "window": window_attn_moe.WindowAttnMoeConfig.tiny(),
}
PREFILLS = {"llama": engine_model.llama_prefill,
            "looped": engine_model.llama_prefill,
            "latent": served_latent.prefill, "hybrid": served_hybrid.prefill,
            "sparse": served_sparse.prefill, "window": served_window.prefill}
OWNED = {"latent": "latent_row", "hybrid": "recurrent_state",
         "sparse": "index_row", "window": "window_rows"}
# the engine options each pinned refusal was taken with
LANES = {
    "speculative_k": dict(speculative_k=2), "step_plans": dict(step_plans=True),
    "fused_prefill": dict(fused_prefill=True),
    "prefix_cache": dict(prefix_cache=True),
    "kv_pager": dict(kv_pager=True, prefix_cache=True),
    "multihost": dict(multihost=True),
    "qos_preempt": dict(qos=True, qos_preempt_prefill=True),
    "int8": dict(kv_dtype="int8"), "bf16": dict(kv_dtype="bfloat16"),
    "f32": dict(kv_dtype="float32"), "none": {},
}
LANES["all"] = dict(speculative_k=2, step_plans=True, fused_prefill=True,
                    prefix_cache=True, kv_pager=True, multihost=True,
                    qos=True, qos_preempt_prefill=True, kv_dtype="float32")
LANES["all8"] = dict(LANES["all"], kv_dtype="int8")


def _as(config_class, cfg):
    """`cfg`'s fields under another config class."""
    return config_class(**{f.name: getattr(cfg, f.name)
                           for f in dataclasses.fields(cfg)})


# -- (a) the lookup ------------------------------------------------------------

@pytest.mark.parametrize("arch", list(TINY))
def test_a_configuration_resolves_to_its_own_entry(arch):
    entry = served(TINY[arch])
    assert isinstance(entry, served_models.ServedModel)
    assert entry.prefill is PREFILLS[arch]
    assert entry is served(dataclasses.replace(TINY[arch]))
    assert {a for a in TINY if served(TINY[a]) is entry} == {
        a for a in TINY if PREFILLS[a] is PREFILLS[arch]}


def test_a_class_nobody_registered_is_refused_by_name():
    @dataclasses.dataclass(frozen=True)
    class Unknown(llama.LlamaConfig):
        pass

    with pytest.raises(TypeError, match="Unknown is no served architecture"):
        served(_as(Unknown, llama.LlamaConfig.tiny()))


# -- (b) a config holds its own attribute and no other's --------------------

@pytest.mark.parametrize("arch", ["llama", "latent", "hybrid", "sparse",
                                  "window"])
def test_a_configuration_holds_no_other_architectures_attribute(arch):
    cfg = TINY[arch]
    for owner, name in OWNED.items():
        assert hasattr(cfg, name) == (owner == arch), (arch, name)
    assert cfg.experts_held == (0 if arch == "llama" else cfg.experts_held)


# -- (c) the refusals, letter for letter ----------------------------------------

@pytest.mark.parametrize("pin", list(PINS["refusals"]))
def test_a_refusal_reads_as_the_parents_did(pin):
    arch, lane = pin.split(".")
    lane, _, mesh = lane.partition("+")
    ecfg = dataclasses.replace(EngineConfig(), **LANES[lane])
    want = PINS["refusals"][pin]
    if want is None:
        _refuse_unwalked_lanes(TINY[arch], ecfg, mesh or None)
        return
    with pytest.raises(ValueError) as err:
        _refuse_unwalked_lanes(TINY[arch], ecfg, mesh or None)
    assert str(err.value) == want


# -- (d) the memory plan's lines, as numbers -----------------------------------

@pytest.mark.parametrize("pin", list(PINS["plans"]))
def test_a_memory_plan_counts_what_the_parents_did(pin):
    arch, kv, quantize = (pin.split(".") + ["int8"])[:3]
    ecfg = dataclasses.replace(
        EngineConfig(), kv_dtype="int8" if kv.startswith("tp") else kv,
        page_size=8, max_seq_len=64, max_batch_size=4, prefill_buckets=(16,),
        quantize_weights=quantize,
        **({} if kv.startswith("tp") else dict(decode_steps_per_dispatch=2)))
    sizes = {"tensor": int(kv[2:])} if kv.startswith("tp") else {}
    plan = memory_plan.plan_engine_memory(
        TINY[arch], ecfg, axis_sizes=sizes, hbm_bytes_per_device=2**30)
    want = PINS["plans"][pin]
    assert [[l.name, l.bytes_per_device] for l in plan.lines] == want["lines"]
    assert (plan.page_bytes_per_device, plan.fit_pages) == (
        want["page"], want["fit"])
    if pin in PINS["notes"]:
        assert [l.note for l in plan.lines] == PINS["notes"][pin]


# -- (e) a sixth architecture, defined here ---------------------------------------

@dataclasses.dataclass(frozen=True)
class SixthConfig(llama.LlamaConfig):
    """A config class of its own with a Llama's fields."""


def _note_sixth(metrics, cfg, lengths, active_mask, K, pool, use_pallas,
                max_pages):
    metrics.sixth_steps += K


@pytest.fixture
def sixth():
    entry = dataclasses.replace(
        served(llama.LlamaConfig.tiny()), name="the sixth",
        caches=lambda cfg: f"model is the sixth ({cfg.n_layers} blocks)",
        lanes=(served_models.MULTIHOST,),
        why_not="those lanes were never written for it",
        counters=("sixth_steps",), note_decode=_note_sixth)
    served_models.register(SixthConfig, entry)
    yield entry
    served_models.register(SixthConfig, None)


def _tokens(cfg, params):
    PS, B = 8, 2
    pool = PagePool.zeros(cfg, 9, PS, dtype=jnp.float32)
    prompts = jnp.asarray([[5, 9, 2, 7] + [0] * 12, [3, 1] + [0] * 14])
    lengths = jnp.asarray([4, 2])
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    zeros, key = jnp.zeros((B,), jnp.float32), jax.random.PRNGKey(1)
    first, pool = engine_model.prefill_batch_step(
        params, cfg, pool, prompts, lengths, tables[:, :2], zeros, zeros,
        jnp.zeros((B,), jnp.int32), key, False)
    block, _, _ = engine_model.decode_multi_step(
        params, cfg, pool, first, tables, lengths + 1,
        jnp.ones((B,), bool), zeros, zeros, jnp.zeros((B,), jnp.int32), key,
        3, False, sampling_flags=(True, False, False))
    return np.asarray(first), np.asarray(block)


def test_a_sixth_entry_is_planned_refused_and_run_with_no_file_edited(sixth):
    plain = llama.LlamaConfig.tiny()
    cfg = _as(SixthConfig, plain)
    assert type(cfg) is SixthConfig and served(cfg) is sixth and served(plain) is not sixth
    ecfg = dataclasses.replace(EngineConfig(), page_size=8, max_seq_len=64,
                               max_batch_size=4, prefill_buckets=(16,))
    plans = [memory_plan.plan_engine_memory(
        c, ecfg, axis_sizes={"tensor": 2}, hbm_bytes_per_device=2**30)
        for c in (cfg, plain)]
    assert plans[0].lines == plans[1].lines
    assert plans[0].pool_pages == plans[1].pool_pages
    # it passes where its lanes are off, and is refused in its own words
    _refuse_unwalked_lanes(cfg, ecfg)
    with pytest.raises(ValueError) as err:
        _refuse_unwalked_lanes(cfg, dataclasses.replace(
            ecfg, multihost=True, prefix_cache=True))
    assert str(err.value) == (
        "model is the sixth (2 blocks); not served with "
        "engine.prefix_cache (prefix-page reuse and the disaggregated KV "
        "transfer), engine.multihost (the multi-host replay): those lanes "
        "were never written for it; turn them off")
    params = llama.init_params(plain, jax.random.PRNGKey(0))
    got, want = _tokens(cfg, params), _tokens(plain, params)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert isinstance(PagePool.zeros(cfg, 3, 8, dtype=jnp.int8),
                      type(PagePool.zeros(plain, 3, 8, dtype=jnp.int8)))


def test_a_sixth_entrys_counter_reaches_the_engine_and_the_fleet(sixth):
    from benchmark.harness.bench_tokenizer import WordTokenizer

    cfg = _as(SixthConfig, llama.LlamaConfig.tiny())
    assert "sixth_steps" in fleet.counter_keys()
    eng = engine.LLMEngine(
        llama.init_params(cfg, jax.random.PRNGKey(0)), cfg,
        WordTokenizer(256), EngineConfig(max_batch_size=2, max_seq_len=32,
                                         page_size=8, prefill_buckets=(16,)))
    assert eng.served is sixth
    snap = eng.metrics.snapshot()
    assert snap["sixth_steps"] == 0 and snap["ssm_slot_writes"] == 0
    eng.start()
    try:
        assert [ev["token_id"] for ev in eng.generate_stream(
            [5, 9, 2], max_new_tokens=4, temperature=0.0)]
    finally:
        eng.stop()
    snap = eng.metrics.snapshot()
    assert snap["sixth_steps"] == snap["decode_steps"] > 0


def test_the_keys_an_entry_adds_go_with_it():
    assert "sixth_steps" not in fleet.counter_keys()
    assert "sixth_steps" not in engine.EngineMetrics().snapshot()
    counters, gauges = served_models.metric_keys()
    assert {"ssm_slot_writes", "sparse_keys_scored"} <= set(counters)
    assert {"ssm_layers", "sparse_topk"} <= set(gauges)
    assert set(counters) <= set(fleet.counter_keys())
    # the window rows' are the scheduler's own while `window_allocator` is
    assert "window_pages_released" in engine.EngineMetrics.SUMMED
    assert set(engine.EngineMetrics.SUMMED) <= set(fleet.counter_keys())
    assert not set(gauges) & set(fleet.counter_keys())
    snap = engine.EngineMetrics().snapshot()
    assert all(snap[k] == 0 for k in counters + gauges)


# -- (f) no consumer names an architecture -----------------------------------------

CONSUMERS = {"engine.py": engine, "engine_model.py": engine_model,
             "memory_plan.py": memory_plan}
DRAWN = ("latent_moe", "hybrid_ssm", "sparse_attn_moe", "window_attn_moe")


@pytest.mark.parametrize("name", list(CONSUMERS) + ["PagePool.zeros"])
def test_a_consumer_names_no_architectures_attribute(name):
    source = inspect.cleandoc(
        inspect.getsource(PagePool.zeros) if name == "PagePool.zeros"
        else inspect.getsource(CONSUMERS[name]))
    for attribute in ("latent_row", "recurrent_state", "index_row"):
        assert attribute not in source, (name, attribute)
    tree = ast.parse(source)
    # `window_rows`: only in the scheduler code keyed on the allocator
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            text = ast.get_source_segment(source, node)
            if "window_rows" in text:
                assert name == "engine.py" and "window_allocator" in text, (
                    name, node.name)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            imported.update([node.module or ""]
                            + [f"{node.module}.{a.name}" for a in node.names])
        elif isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
    for module in DRAWN:
        assert not [i for i in imported if i.split(".")[-1] == module], (
            name, module)
