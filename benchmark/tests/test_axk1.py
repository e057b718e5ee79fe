"""The axk1 entry: the configuration file against the catalog's keys,
its counts of a step's work against counts worked by hand for A.X-K1's
share of a 16-chip group, its two readers, and a tiny configuration of
the same keys through `run_cell` on the CPU (a rehearsal, never a
measurement)."""

import json
import os

import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.architectures import axk1 as entry
from benchmark.harness import roofline, xplane
from benchmark.readers import engine_flight_median, trace_moe_kernel
from benchmark.tests import test_rehearsal as tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
CELL = "ax-k1-ep16.decode-closed128"


@pytest.fixture(scope="module")
def k1():
    with open(os.path.join(BENCH_DIR, "configs",
                           "ax-k1-int8-ep16.json")) as fh:
        return json.load(fh)


def tiny_file():
    """A.X-K1's keys at a tiny size: 3 layers (one dense), 16 experts of
    which 4 are held from expert 4 on, 4 a token."""
    return {
        "architecture": "axk1", "model_type": "axk1", "hidden_size": 64,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": 4, "expert_offset": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "topk_method": "none", "vocab_size": 512,
        "max_position_embeddings": 256, "rope_theta": 10000,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 32},
        "published": {"num_hidden_layers": 3, "n_routed_experts": 16,
                      "vocab_size": 512},
        "serving": {"chips": 1, "dtype": "float32",
                    "quantize_weights": "int8", "kv_dtype": "float32",
                    "n_pages": 64,
                    "engine": {"max_batch_size": 4, "max_seq_len": 256,
                               "page_size": 16, "prefill_buckets": [32, 128],
                               "max_prefill_group": 2,
                               "decode_steps_per_dispatch": 2}},
        "reference_check": {"prompt_tokens": 12, "new_tokens": 3,
                            "rel_tol": 0.05},
    }


def test_the_file_keeps_every_published_key_but_the_three_it_cuts(k1):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "A.X-K1")
    assert k1["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if k1.get(k) != v)
    assert differs == sorted(k1["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert k1["published"] == {k: row["config"][k] for k in k1["reduced"]}
    assert architectures.load(k1) is entry
    mcfg = entry.model_config(k1)
    assert (mcfg.dim, mcfg.n_layers, mcfg.n_dense_layers, mcfg.n_heads,
            mcfg.q_lora_rank, mcfg.latent_row, mcfg.qk_nope_head_dim,
            mcfg.v_head_dim, mcfg.mlp_dim, mcfg.moe_mlp_dim) == (
        7168, 15, 1, 64, 1536, (512, 64), 128, 128, 18432, 2048)
    assert (mcfg.n_routed_experts, mcfg.n_experts_per_tok, mcfg.experts_held,
            mcfg.expert_offset, mcfg.vocab_size) == (192, 8, 12, 0, 20480)
    assert mcfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.3466 ** 2,
                                               rel=1e-4)
    assert entry.step_kernel_calls(k1) == 15


def test_parameter_counts(k1):
    # ISSUE 33's reckoning: 11.01 + 18.87 + 4.13 + 8.39 + 58.72 = 101.1 M
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
            + 64 * 128 * 7168)
    assert entry.attention_params(k1) == attn == 101_122_048
    assert entry.expert_params(k1) == 3 * 7168 * 2048 == 44_040_192
    assert entry.head_params(k1) == 7168 * 20480 == 146_800_640
    dense = 3 * 7168 * 18432
    assert entry.always_read_params(k1) == (
        15 * attn + dense + 14 * 44_040_192 + 146_800_640)
    # an expert layer HERE: attention, shared expert, twelve experts
    assert attn + 13 * 44_040_192 == 673_644_544  # 0.674 GB, router apart
    assert entry.kv_bytes_per_token(k1) == 15 * 576 * 2 == 17_280
    assert k1["serving"]["n_pages"] * 128 * 17_280 == 3_114_270_720


def test_expected_experts_hit(k1):
    assert entry.local_share(k1) == 12 / 192
    # 128 tokens: a held expert is missed with probability (23/24)^128
    assert entry.experts_hit(k1, 128) == pytest.approx(
        12 * (1 - (23 / 24) ** 128))
    assert entry.experts_hit(k1, 128) == pytest.approx(11.95, abs=0.01)
    assert entry.experts_hit(k1, 1) == pytest.approx(0.5)
    assert entry.experts_hit(k1, 0) == 0


def test_decode_step_is_memory_bound_and_counts_the_experts_hit(k1):
    work = entry.decode_step(k1, batch=128, context=640)
    weights = entry.always_read_params(k1) \
        + 14 * entry.experts_hit(k1, 128) * 44_040_192
    router = 14 * 7168 * 192 * 2
    assert work["bytes"] == pytest.approx(
        weights + router + 128 * 641 * 17_280)
    pairs = 128 * 8 * 12 / 192
    assert work["flops"] == pytest.approx(
        2 * 128 * entry.always_read_params(k1)
        + 2 * pairs * 44_040_192 * 14
        + 128 * 640 * 15 * (2 * 64 * 576 + 2 * 64 * 512)
        + 128 * 15 * 2 * 64 * 512 * 256)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(0.0140, rel=2e-2)  # 11.5 GB


def test_prefill_reads_the_experts_its_tokens_hit(k1):
    work = entry.prefill(k1, prompt_tokens=1536, mean_prompt=384, programs=1)
    assert work["bytes"] == pytest.approx(
        entry.always_read_params(k1) + 14 * 7168 * 192 * 2
        + 14 * entry.experts_hit(k1, 1536) * 44_040_192 + 1536 * 17_280)
    body = entry.always_read_params(k1) - entry.head_params(k1)
    assert work["flops"] == pytest.approx(
        2 * 1536 * body + 2 * 1536 * 8 * (12 / 192) * 44_040_192 * 14
        + 1536 * 384 * 64 * (192 + 128) * 15
        + 2 * 4 * entry.head_params(k1))


def test_kernel_work_functions(k1):
    # the absorbed kernel: about 121 flop a cached byte
    att = entry.attention_kernel(k1, calls=15, batch=128, context=640)
    assert att["flops"] == pytest.approx(
        15 * 128 * 640 * (2 * 64 * 576 + 2 * 64 * 512))
    assert att["bytes"] == pytest.approx(
        15 * 128 * (640 * 1152 + 64 * (576 + 512) * 2))
    assert 100 < att["flops"] / att["bytes"] < 121
    # the grouped matmul: two calls an expert layer
    gmm = entry.moe_kernel(k1, calls=28, batch=128)
    assert gmm["flops"] == pytest.approx(14 * 2 * 64 * 44_040_192)
    assert gmm["bytes"] == pytest.approx(
        14 * (entry.experts_hit(k1, 128) * 44_040_192
              + 64 * (7168 + 3 * 2048 + 7168) * 2))
    step = entry.decode_step(k1, 128, 640)
    assert 0.62 < gmm["bytes"] / step["bytes"] < 0.66


def test_moe_kernel_reader_on_a_recorded_trace():
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
    work = entry.moe_kernel(config, 12, 4.0)
    want = 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
    assert trace_moe_kernel.read(ctx, *args) == pytest.approx(want)
    # a program without the kernel (every one before sparse experts), an
    # entry without the function, no trace: nothing, and no raise
    assert trace_moe_kernel.read(ctx, "decode_multi_step",
                                 "moe_grouped_matmul") is None
    assert trace_moe_kernel.read(dict(ctx, config=tiny.TINY), *args) is None
    assert trace_moe_kernel.read(dict(ctx, trace=None), *args) is None


def test_flight_median_reader():
    events = [{"kind": 19, "t": t, "a": a, "b": 1.0 + a / 100}
              for t, a in ((-1.0, 99.0), (0.5, 40.0), (1.0, 60.0),
                           (2.0, 50.0), (9.0, 7.0))]
    events.append({"kind": 3, "t": 1.0, "a": 1e6, "b": 0.0})
    ctx = {"engine": {"events": events}, "seconds": 3.0,
           "config": {"n_routed_experts": 4}}
    assert engine_flight_median.read(ctx, "moe_load", "a") == 50.0
    assert engine_flight_median.read(
        ctx, "moe_load", "a", per_config_key="n_routed_experts") == 12.5
    assert engine_flight_median.read(ctx, "moe_load", "b") == 1.5
    assert engine_flight_median.read(
        {"engine": {"events": events[-1:]}, "seconds": 3.0, "config": {}},
        "moe_load", "a") is None


def test_tiny_cell_through_run_cell():
    bench = bench_run.load_benchmark()
    metrics = (bench_run.cell_metrics(bench, CELL, False)
               + bench_run.cell_metrics(bench, CELL, True))
    assert {"closed.moe_kernel_share", "closed.moe_kernel_roofline",
            "closed.moe.pairs_per_expert_step",
            "closed.moe.load_max_over_mean"} <= {m["name"] for m in metrics}
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED, metrics,
        seed=2**31 + 33, seconds=3.0, trace=False, allow_cpu=True)
    json.dumps(out)
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_asked"] == out["checks"]["tokens_generated"]
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert 0 < out["metrics"]["closed.sched.occupancy"]["value"] <= 4
    # 4 of 16 experts held, 4 choices a token: a pair a token and layer
    # falls here, a quarter of it on each held expert
    per = out["metrics"]["closed.moe.pairs_per_expert_step"]["value"]
    assert 0 < per <= 4
    assert out["metrics"]["closed.moe.load_max_over_mean"]["value"] >= 1.0


def test_a_reference_of_another_share_reads_not_correct(monkeypatch):
    """The served model holds experts 4..7; a reference told 8..11
    disagrees."""
    real = entry.reference_logits
    monkeypatch.setattr(
        entry, "reference_logits",
        lambda config, params, ids: real(dict(config, expert_offset=8),
                                         params, ids))
    bench = bench_run.load_benchmark()
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED,
        bench_run.cell_metrics(bench, CELL, False), seed=2**31 + 35,
        seconds=2.0, trace=False, allow_cpu=True)
    assert out["failed"] == 0
    assert not out["checks"]["reference"]["ok"] and not out["correct"]
