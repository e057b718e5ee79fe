"""The kimilinear entry: the configuration file against the catalog's keys,
its counts of a step's work against counts worked by hand for
Kimi-Linear-48B-A3B's share of an 8-chip group, the state-update kernel's
and the state-cache event's readers, and a tiny configuration of the same
keys through `run_cell` on the CPU (a rehearsal, never a measurement)."""

import json
import os

import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.architectures import kimilinear as entry
from benchmark.harness import roofline, xplane
from benchmark.readers import engine_state_cache, trace_ssm_kernel
from benchmark.tests import test_rehearsal as tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
CELL = "kimi-linear-48b-ep8.reason-closed80"


@pytest.fixture(scope="module")
def kimi():
    with open(os.path.join(BENCH_DIR, "configs",
                           "kimi-linear-48b-a3b-int8-ep8.json")) as fh:
        return json.load(fh)


def tiny_file():
    """The source's keys at a tiny size: KDA, KDA, MLA, KDA, MLA; a dense
    layer then four of 16 routed experts of which 4 are held (4..7)."""
    return {
        "architecture": "kimilinear", "model_type": "kimi_linear",
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 5,
        "first_k_dense_replace": 1,
        "linear_attn_config": {
            "kda_layers": [1, 2, 4], "full_attn_layers": [3, 5],
            "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
        "num_experts": 4, "expert_offset": 4,
        "published": {"num_experts": 16, "vocab_size": 512},
        "num_experts_per_token": 4, "num_shared_experts": 1,
        "routed_scaling_factor": 2.446, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
        "topk_group": 1, "use_grouped_topk": True, "moe_layer_freq": 1,
        "num_nextn_predict_layers": 0, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "rope_scaling": None, "vocab_size": 512,
        "model_max_length": 256, "tie_word_embeddings": False,
        "serving": {"chips": 1, "dtype": "float32",
                    "quantize_weights": "int8", "kv_dtype": "float32",
                    "n_pages": 64, "kda_chunk": 8, "kda_sub": 4,
                    "engine": {"max_batch_size": 4, "max_seq_len": 256,
                               "page_size": 16, "prefill_buckets": [32, 128],
                               "max_prefill_group": 2,
                               "decode_steps_per_dispatch": 2}},
        "reference_check": {"prompt_tokens": 12, "new_tokens": 3,
                            "rel_tol": 0.05},
    }


def test_the_file_keeps_every_published_key_but_the_share(kimi):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert kimi["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if kimi.get(k) != v)
    assert differs == sorted(kimi["reduced"]) == ["num_experts", "vocab_size"]
    assert kimi["published"] == {"num_experts": 256, "vocab_size": 163840}
    assert (kimi["num_experts"], kimi["expert_offset"],
            kimi["vocab_size"]) == (32, 0, 20480)
    assert architectures.load(kimi) is entry
    mcfg = entry.model_config(kimi)
    # ALL 27 layers, the published pattern: [KDA, KDA, KDA, MLA] six
    # times, then [KDA, KDA, MLA]
    assert mcfg.layer_types == (("kda",) * 3 + ("mla",)) * 6 + (
        "kda", "kda", "mla")
    assert (mcfg.dim, mcfg.n_layers, mcfg.n_kda_layers, mcfg.cache_rows,
            mcfg.n_dense_layers, mcfg.n_moe_layers) == (2304, 27, 20, 7, 1,
                                                        26)
    assert (mcfg.kda_heads, mcfg.kda_head_dim, mcfg.kda_conv, mcfg.kda_rank,
            mcfg.d_inner) == (32, 128, 4, 128, 4096)
    assert (mcfg.n_heads, mcfg.q_lora_rank, mcfg.latent_row,
            mcfg.qk_nope_head_dim, mcfg.v_head_dim, mcfg.rotary) == (
        32, None, (512, 64), 128, 128, False)
    assert mcfg.softmax_scale == 192 ** -0.5
    assert (mcfg.n_routed_experts, mcfg.experts_held, mcfg.expert_offset,
            mcfg.n_experts_per_tok, mcfg.moe_mlp_dim, mcfg.mlp_dim,
            mcfg.routed_scaling_factor, mcfg.vocab_size) == (
        256, 32, 0, 8, 1024, 9216, 2.446, 20480)
    assert entry.step_kernel_calls(kimi) == 7
    # 43.4 MB a decode slot: twenty float32 states of 2 MiB and bf16 tails
    rs = mcfg.recurrent_state
    assert (rs.layers, rs.heads, rs.head_dim, rs.state, rs.tail,
            rs.conv_width) == (20, 32, 128, 128, 3, 12288)
    assert rs.bytes_per_slot == 20 * (2 * 2**20 + 3 * 12288 * 2) \
        == 43_417_600
    # the floors of a share: 32 >= 8 experts, an eighth of the vocabulary
    assert kimi["num_experts"] >= 8 and 8 * kimi["vocab_size"] == 163840


def test_parameter_counts(kimi):
    # ISSUE 48's reckoning: 39.5 M a KDA mixer, 29.1 M a latent one
    assert entry.kda_params(kimi) == 4 * 2304 * 4096 + 2 * (
        2304 * 128 + 128 * 4096) + 2304 * 32 == 39_460_864
    assert entry.mla_params(kimi) == 2304 * 6144 + 2304 * 576 \
        + 512 * 8192 + 4096 * 2304 == 29_114_368
    assert entry.expert_params(kimi) == 3 * 2304 * 1024 == 7_077_888
    assert entry.head_params(kimi) == 2304 * 20480
    dense = 3 * 2304 * 9216
    assert dense == 63_700_992
    assert entry.always_read_params(kimi) == (
        20 * 39_460_864 + 7 * 29_114_368 + dense + 26 * 7_077_888
        + 2304 * 20480)
    # every weight of the cut, int8: 7.1 GB + the bf16 embedding
    whole = entry.always_read_params(kimi) + 26 * 32 * 7_077_888
    assert whole == pytest.approx(7.16e9, rel=5e-3)
    assert entry.state_bytes_per_sequence(kimi) == 2 * 2**20
    assert entry.tail_bytes_per_sequence(kimi) == 3 * 12288 * 2
    assert entry.kv_bytes_per_token(kimi) == 7 * 576 * 2


def test_expected_experts_hit(kimi):
    # 80 tokens: a held expert is missed with probability (31/32)^80
    assert entry.experts_hit(kimi, 80) == pytest.approx(
        32 * (1 - (31 / 32) ** 80))
    assert 0.91 < entry.experts_hit(kimi, 80) / 32 < 0.93
    assert entry.experts_hit(kimi, 1) == pytest.approx(1.0)
    assert entry.experts_hit(kimi, 0) == 0
    assert entry.local_share(kimi) == 1 / 8


def test_decode_step_is_memory_bound_and_the_state_is_its_largest_term(kimi):
    work = entry.decode_step(kimi, batch=80, context=2560)
    state = 80 * 20 * 2 * (2 * 2**20 + 3 * 12288 * 2)
    experts = 26 * entry.experts_hit(kimi, 80) * 7_077_888
    rows = 80 * 2561 * 7 * 576 * 2
    assert work["bytes"] == pytest.approx(
        entry.always_read_params(kimi) + entry.small_bytes(kimi) + state
        + experts + rows)
    assert state == pytest.approx(6.95e9, rel=2e-3)
    assert experts == pytest.approx(5.42e9, rel=5e-3)
    assert rows == pytest.approx(1.65e9, rel=5e-3)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(0.0186, rel=3e-2)  # 15.2 GB
    assert state > experts > rows


def test_kernel_work_functions(kimi):
    kda = entry.ssm_kernel(kimi, calls=20, batch=80)
    assert kda["bytes"] == pytest.approx(
        20 * 80 * (2 * 2 * 2**20 + 4 * 6 * 4096))
    assert kda["flops"] == pytest.approx(20 * 80 * 7 * 4096 * 128)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    one = roofline.least_seconds(entry.ssm_kernel(kimi, 1, 80), peaks)
    assert one["bound"] == "memory"
    assert one["seconds"] == pytest.approx(0.42e-3, rel=3e-2)
    gmm = entry.moe_kernel(kimi, calls=52, batch=80)
    assert gmm["flops"] == pytest.approx(26 * 2 * 80 * 7_077_888)
    assert gmm["bytes"] == pytest.approx(
        26 * (entry.experts_hit(kimi, 80) * 7_077_888
              + 80 * (2304 + 3 * 1024 + 2304) * 2))
    att = entry.attention_kernel(kimi, calls=7, batch=80, context=2560)
    assert att["bytes"] == pytest.approx(
        7 * 80 * (2560 * 576 * 2 + 32 * (576 + 512) * 2))


def test_prefill_writes_each_sequences_state_once(kimi):
    work = entry.prefill(kimi, prompt_tokens=1536, mean_prompt=1536,
                         programs=1)
    assert work["bytes"] == pytest.approx(
        entry.always_read_params(kimi) + entry.small_bytes(kimi)
        + 26 * entry.experts_hit(kimi, 1536) * 7_077_888
        + 1536 * 7 * 576 * 2 + 20 * (2 * 2**20 + 3 * 12288 * 2))


def test_the_kda_kernels_reader_on_a_recorded_trace():
    """data/tiny.xplane.pb: three executions of `decode_multi_step`, four
    `convolution_tanh_fusion` calls each; here that op plays the kernel.
    `trace_ssm_kernel`, unedited, finds the entry's `ssm_kernel` and the
    kernel by the name the metric's file gives."""
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
    # a program without the kernel (every one before this model), and
    # granite's kernel's name, which this model's programs do not hold
    assert trace_ssm_kernel.read(ctx, "decode_multi_step",
                                 "kda_state_update") is None
    with open(os.path.join(BENCH_DIR, "metrics",
                           "closed.kda_kernel_roofline.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "trace_ssm_kernel"
    assert spec["params"]["kernel"] == "kda_state_update"
    assert "ssm_state_update" not in spec["params"]["kernel"]
    assert trace_ssm_kernel.read(dict(ctx, trace=None), *args) is None


def test_the_state_cache_events_reader():
    events = [{"kind": 24, "t": 1.0, "a": 2000.0, "b": 0.29},
              {"kind": 24, "t": 2.0, "a": 2008.0, "b": 0.30},
              {"kind": 24, "t": 3.0, "a": 2016.0, "b": 0.31},
              {"kind": 24, "t": -1.0, "a": 10.0, "b": 0.9},   # the ramp's
              {"kind": 23, "t": 1.5, "a": 1.0, "b": 1.0}]
    ctx = {"engine": {"events": events}, "seconds": 10.0}
    assert engine_state_cache.read(ctx, "a") == 2008.0
    assert engine_state_cache.read(ctx, "b") == 0.30
    # an engine that writes no such event (every other model, the parent)
    ctx = {"engine": {"events": events[-1:]}, "seconds": 10.0}
    assert engine_state_cache.read(ctx, "a") is None


def test_the_cell_lists_its_metrics_and_no_other_models():
    bench = bench_run.load_benchmark()
    names = {m["name"] for m in bench_run.cell_metrics(bench, CELL, False)
             + bench_run.cell_metrics(bench, CELL, True)}
    assert {"out_tokens_per_s", "setup_s", "closed.kda_kernel_share",
            "closed.kda_kernel_roofline", "closed.latent.context_tokens",
            "closed.seq.cache_share_of_sequence_bytes",
            "closed.decode_step_roofline", "closed.moe_kernel_roofline",
            "closed.attention_kernel_share", "closed.moe.load_max_over_mean",
            "closed.sched.occupancy", "closed.step.decode_ms"} <= names
    assert not names & {
        "closed.attention_kernel_roofline", "closed.ssm_kernel_share",
        "closed.ssm_kernel_roofline", "closed.moe.pairs_per_expert_step",
        "closed.slot.retire_to_admit_p50_ms",
        "closed.slot.admit_to_decode_p50_ms"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "reason-closed80")


def test_tiny_cell_through_run_cell():
    bench = bench_run.load_benchmark()
    metrics = (bench_run.cell_metrics(bench, CELL, False)
               + bench_run.cell_metrics(bench, CELL, True))
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED, metrics,
        seed=2**31 + 48, seconds=3.0, trace=False, allow_cpu=True)
    json.dumps(out)
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_asked"] == out["checks"]["tokens_generated"]
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert 0 < out["metrics"]["closed.sched.occupancy"]["value"] <= 4
    assert out["metrics"]["closed.moe.load_max_over_mean"]["value"] >= 1.0
    # the state-cache event: contexts of the tiny mix, and the share of a
    # sequence's bytes that is latent rows
    assert 8 < out["metrics"]["closed.latent.context_tokens"]["value"] < 40
    assert 0 < out["metrics"][
        "closed.seq.cache_share_of_sequence_bytes"]["value"] < 1


def test_a_reference_that_forgets_its_state_reads_not_correct(monkeypatch):
    """The comparison can tell: a reference whose KDA layers decay their
    state to nothing every token (A_log + 8: a < 0.05) disagrees with what
    is served, so a served state that was dropped, zeroed or left in its
    predecessor's slot would too."""
    real = entry.reference_logits

    def forgets(config, params, ids):
        kda = dict(params["kda"], A_log=params["kda"]["A_log"] + 8.0)
        return real(config, dict(params, kda=kda), ids)

    monkeypatch.setattr(entry, "reference_logits", forgets)
    bench = bench_run.load_benchmark()
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED,
        bench_run.cell_metrics(bench, CELL, False), seed=2**31 + 49,
        seconds=2.0, trace=False, allow_cpu=True)
    assert out["failed"] == 0
    assert not out["checks"]["reference"]["ok"] and not out["correct"]
