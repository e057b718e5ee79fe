"""The granitemoehybrid entry: the configuration file against the
catalog's keys, its counts of a step's work against counts worked by hand
for one period of granite-4.0-h-small, the state-update kernel's reader,
and a tiny configuration of the same keys through `run_cell` on the CPU
(a rehearsal, never a measurement)."""

import json
import os

import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.architectures import granitemoehybrid as entry
from benchmark.harness import roofline, xplane
from benchmark.readers import trace_ssm_kernel
from benchmark.tests import test_rehearsal as tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
CELL = "granite4h-small.decode-closed96"


@pytest.fixture(scope="module")
def g4():
    with open(os.path.join(BENCH_DIR, "configs",
                           "granite-4.0-h-small-int8.json")) as fh:
        return json.load(fh)


def tiny_file():
    """The source's keys at a tiny size: a state-space layer on each side
    of an attention layer, 8 experts of which 3 a token."""
    return {
        "architecture": "granitemoehybrid", "model_type": "granitemoehybrid",
        "hidden_size": 64, "intermediate_size": 32,
        "shared_intermediate_size": 48, "num_hidden_layers": 3,
        "layer_types": ["mamba", "attention", "mamba"],
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "attention_bias": False,
        "position_embedding_type": "nope", "num_local_experts": 8,
        "num_experts_per_tok": 3, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
        "logits_scaling": 16, "rms_norm_eps": 1e-5, "vocab_size": 512,
        "max_position_embeddings": 256, "tie_word_embeddings": True,
        "serving": {"chips": 1, "dtype": "float32",
                    "quantize_weights": "int8", "kv_dtype": "int8",
                    "n_pages": 64, "ssm_chunk": 8,
                    "engine": {"max_batch_size": 4, "max_seq_len": 256,
                               "page_size": 16, "prefill_buckets": [32, 128],
                               "max_prefill_group": 2,
                               "decode_steps_per_dispatch": 2}},
        "reference_check": {"prompt_tokens": 12, "new_tokens": 3,
                            "rel_tol": 0.05},
    }


def test_the_file_keeps_every_published_key_but_the_depth(g4):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "granite-4.0-h-small")
    assert g4["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if g4.get(k) != v)
    assert differs == sorted(g4["reduced"]) == [
        "layer_types", "num_hidden_layers"]
    # one whole period in its published order: the first ten layers
    assert g4["layer_types"] == row["config"]["layer_types"][:10]
    assert g4["published"]["num_hidden_layers"] == 40
    assert architectures.load(g4) is entry
    mcfg = entry.model_config(g4)
    assert (mcfg.dim, mcfg.n_layers, mcfg.n_ssm_layers, mcfg.cache_rows,
            mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim) == (
        4096, 10, 9, 1, 32, 8, 128)
    assert (mcfg.ssm_heads, mcfg.ssm_head_dim, mcfg.ssm_state, mcfg.ssm_conv,
            mcfg.d_inner, mcfg.conv_width) == (128, 64, 128, 4, 8192, 8448)
    assert (mcfg.n_experts, mcfg.n_experts_per_tok, mcfg.moe_mlp_dim,
            mcfg.shared_mlp_dim, mcfg.vocab_size) == (72, 10, 768, 1536,
                                                      100352)
    assert (mcfg.embedding_multiplier, mcfg.residual_multiplier,
            mcfg.attention_multiplier, mcfg.logits_scaling) == (
        12.0, 0.22, 1 / 128, 16.0)
    assert mcfg.layer_types.index("attention") == 5
    assert entry.step_kernel_calls(g4) == 1
    # 38.2 MB a decode slot: nine float32 states and bf16 tails
    assert mcfg.recurrent_state.bytes_per_slot == 9 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2) == 38_204_928


def test_parameter_counts(g4):
    # ISSUE 35's reckoning: 68.68 + 33.55 M a state-space mixer
    assert entry.ssm_params(g4) == 4096 * 16768 + 8192 * 4096 == 102_236_160
    assert entry.attention_params(g4) == 2 * 4096 * 4096 + 2 * 4096 * 1024 \
        == 41_943_040
    assert entry.shared_params(g4) == 3 * 4096 * 1536 == 18_874_368
    assert entry.expert_params(g4) == 3 * 4096 * 768 == 9_437_184
    assert entry.head_params(g4) == 4096 * 100352 == 411_041_792
    assert entry.always_read_params(g4) == (
        9 * 102_236_160 + 41_943_040 + 10 * 18_874_368 + 411_041_792)
    # a period of ten with every expert: 7.95 G parameters
    period = entry.always_read_params(g4) - entry.head_params(g4) \
        + 10 * 72 * 9_437_184
    assert period == pytest.approx(7.95e9, rel=2e-3)
    assert entry.state_bytes_per_sequence(g4) == 4_194_304
    assert entry.kv_bytes_per_token(g4) == 2 * 8 * (128 + 4) == 2112


def test_expected_experts_hit(g4):
    # 96 tokens: an expert is missed with probability (62/72)^96
    assert entry.experts_hit(g4, 96) == pytest.approx(
        72 * (1 - (62 / 72) ** 96))
    assert entry.experts_hit(g4, 96) > 71.99
    assert entry.experts_hit(g4, 1) == pytest.approx(10.0)
    assert entry.experts_hit(g4, 0) == 0


def test_decode_step_is_memory_bound_and_half_of_it_is_the_mixer(g4):
    work = entry.decode_step(g4, batch=96, context=640)
    weights = entry.always_read_params(g4) \
        + 10 * entry.experts_hit(g4, 96) * 9_437_184
    state = 96 * 9 * 2 * (4_194_304 + 3 * 8448 * 2)
    assert work["bytes"] == pytest.approx(
        weights + entry.small_bytes(g4) + state + 96 * 641 * 2112)
    assert state == pytest.approx(7.34e9, rel=2e-3)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(0.0193, rel=3e-2)  # 15.8 GB
    mixer = state + 9 * 102_236_160
    assert 0.49 < mixer / work["bytes"] < 0.53


def test_kernel_work_functions(g4):
    ssm = entry.ssm_kernel(g4, calls=9, batch=96)
    assert ssm["bytes"] == pytest.approx(
        9 * 96 * (2 * 4_194_304 + 4 * (128 * 128 + 2 * 8192 + 256)))
    assert ssm["flops"] == pytest.approx(9 * 96 * 6 * 8192 * 128)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    one = roofline.least_seconds(entry.ssm_kernel(g4, 1, 96), peaks)
    assert one["bound"] == "memory"
    assert one["seconds"] == pytest.approx(0.98e-3, rel=3e-2)
    gmm = entry.moe_kernel(g4, calls=20, batch=96)
    assert gmm["flops"] == pytest.approx(10 * 2 * 960 * 9_437_184)
    assert gmm["bytes"] == pytest.approx(
        10 * (entry.experts_hit(g4, 96) * 9_437_184
              + 960 * (4096 + 3 * 768 + 4096) * 2))
    att = entry.attention_kernel(g4, calls=1, batch=96, context=640)
    assert att["bytes"] == pytest.approx(
        96 * (640 * 2112 + 2 * 32 * 128 * 2))
    step = entry.decode_step(g4, 96, 640)
    assert 0.42 < gmm["bytes"] / step["bytes"] < 0.46


def test_prefill_writes_each_sequences_state_once(g4):
    work = entry.prefill(g4, prompt_tokens=1536, mean_prompt=384, programs=1)
    assert work["bytes"] == pytest.approx(
        entry.always_read_params(g4) + entry.small_bytes(g4)
        + 10 * entry.experts_hit(g4, 1536) * 9_437_184 + 1536 * 2112
        + 4 * 9 * (4_194_304 + 3 * 8448 * 2))


def test_ssm_kernel_reader_on_a_recorded_trace():
    """data/tiny.xplane.pb: three executions of `decode_multi_step`, four
    `convolution_tanh_fusion` calls each; here that op plays the
    kernel."""
    trace = xplane.reduce(xplane.load(os.path.join(DATA, "tiny.xplane.pb")))
    config = tiny_file()
    ctx = {"trace": trace, "config": config, "chips": 1,
           "peaks": roofline.load_peaks(BENCH_DIR, "TPU v5 lite"),
           "engine": {"trace_open": {"decode_steps": 0, "busy_slots_acc": 0},
                      "trace_close": {"decode_steps": 2,
                                      "busy_slots_acc": 8}}}
    args = ("decode_multi_step", "convolution_tanh_fusion")
    device_s = sum(s for k, s in trace["ops"].items()
                   if k == "decode_multi_step/convolution_tanh_fusion")
    work = entry.ssm_kernel(config, 12, 4.0)
    want = 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
    assert trace_ssm_kernel.read(ctx, *args) == pytest.approx(want)
    # a program without the kernel (every one before state-space layers),
    # an entry without the function, no trace: nothing, and no raise
    assert trace_ssm_kernel.read(ctx, "decode_multi_step",
                                 "ssm_state_update") is None
    assert trace_ssm_kernel.read(dict(ctx, config=tiny.TINY), *args) is None
    assert trace_ssm_kernel.read(dict(ctx, trace=None), *args) is None


def test_tiny_cell_through_run_cell():
    bench = bench_run.load_benchmark()
    metrics = (bench_run.cell_metrics(bench, CELL, False)
               + bench_run.cell_metrics(bench, CELL, True))
    assert {"closed.ssm_kernel_share", "closed.ssm_kernel_roofline",
            "closed.moe_kernel_roofline", "closed.attention_kernel_roofline",
            "closed.decode_step_roofline", "closed.step.decode_ms",
            "closed.moe.load_max_over_mean", "closed.sched.occupancy"} <= {
        m["name"] for m in metrics}
    assert "closed.moe.pairs_per_expert_step" not in {
        m["name"] for m in metrics}
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED, metrics,
        seed=2**31 + 35, seconds=3.0, trace=False, allow_cpu=True)
    json.dumps(out)
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_asked"] == out["checks"]["tokens_generated"]
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert 0 < out["metrics"]["closed.sched.occupancy"]["value"] <= 4
    assert out["metrics"]["closed.moe.load_max_over_mean"]["value"] >= 1.0


def test_a_reference_without_the_skip_term_reads_not_correct(monkeypatch):
    """The comparison can tell: a reference whose state-space layers
    leave out D * x disagrees with what is served."""
    real = entry.reference_logits

    def without_skip(config, params, ids):
        ssm = dict(params["ssm"], D=params["ssm"]["D"] * 0.0)
        return real(config, dict(params, ssm=ssm), ids)

    monkeypatch.setattr(entry, "reference_logits", without_skip)
    bench = bench_run.load_benchmark()
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED,
        bench_run.cell_metrics(bench, CELL, False), seed=2**31 + 37,
        seconds=2.0, trace=False, allow_cpu=True)
    assert out["failed"] == 0
    assert not out["checks"]["reference"]["ok"] and not out["correct"]
