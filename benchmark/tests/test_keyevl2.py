"""The keyevl2 entry: the configuration file against the catalog's keys,
its counts of a step's work against counts worked by hand for one
pipeline stage of Keye-VL-2.0-30B-A3B (ISSUE 42's bytes), the two new
readers, the cell's files by the names in BENCHMARK.json, and a tiny
configuration of the same keys through `run_cell` on the CPU (a
rehearsal, never a measurement)."""

import json
import os

import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.architectures import keyevl2 as entry
from benchmark.harness import roofline, xplane
from benchmark.readers import engine_sparse_select, trace_index_kernel
from benchmark.tests import test_rehearsal as tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
CELL = "keye2-30b-a3b.longdoc-closed16"
CONFIG = "keye-vl-2.0-30b-a3b-int8"


@pytest.fixture(scope="module")
def k2():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")) as fh:
        return json.load(fh)


def tiny_file():
    """The source's keys at a tiny size: 3 layers, 4/2 heads of 16, an
    indexer of 4 heads of 8 with topk 16 in tiles of 8, 8 experts of
    which 2 a token."""
    return {
        "architecture": "keyevl2", "model_type": "KeyeVL2",
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 2,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "attention_bias": False,
        "use_sliding_window": False, "sliding_window": None,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                      "q_chunk_size": 8, "topk": 16},
        "rope_theta": 1e7, "rms_norm_eps": 1e-6, "vocab_size": 512,
        "max_position_embeddings": 256, "tie_word_embeddings": False,
        "serving": {"chips": 1, "dtype": "float32",
                    "quantize_weights": "int8", "kv_dtype": "int8",
                    "n_pages": 64,
                    "engine": {"max_batch_size": 4, "max_seq_len": 128,
                               "page_size": 8, "prefill_buckets": [32, 64],
                               "max_prefill_group": 1,
                               "decode_steps_per_dispatch": 2}},
        # past topk, so that the check runs the selection
        "reference_check": {"prompt_tokens": 40, "new_tokens": 3,
                            "rel_tol": 0.05},
    }


def test_the_file_keeps_every_published_key_but_the_depth(k2):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert k2["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if k2.get(k) != v)
    assert differs == k2["reduced"] == ["num_hidden_layers"]
    assert k2["num_hidden_layers"] == 12
    assert k2["published"] == {"num_hidden_layers": 48}
    assert k2["sa_config"] == row["config"]["sa_config"]
    for reading in ("qk_norm", "rotary", "indexer", "chunk sizes",
                    "selection", "experts", "index key type",
                    "vision tower", "weights", "page pool"):
        assert reading in k2["assumed"], reading
    assert "pipeline of four" in k2["deployment"]
    assert architectures.load(k2) is entry
    mcfg = entry.model_config(k2)
    assert (mcfg.dim, mcfg.n_layers, mcfg.cache_rows, mcfg.n_heads,
            mcfg.n_kv_heads, mcfg.head_dim, mcfg.vocab_size) == (
        2048, 12, 12, 32, 4, 128, 151936)
    assert (mcfg.index_heads, mcfg.index_head_dim, mcfg.index_topk,
            mcfg.index_row, mcfg.prefill_tile) == (16, 64, 2048, 64, 512)
    assert (mcfg.n_experts, mcfg.experts_held, mcfg.n_experts_per_tok,
            mcfg.moe_mlp_dim) == (128, 128, 8, 768)
    assert (mcfg.rope_theta, mcfg.rms_eps) == (1e7, 1e-6)
    assert entry.step_kernel_calls(k2) == 12
    s = k2["serving"]
    assert (s["n_pages"], s["engine"]["max_batch_size"],
            s["engine"]["max_seq_len"], s["engine"]["page_size"],
            s["engine"]["max_prefill_group"]) == (2688, 16, 19456, 128, 1)
    # the check that decides `correct` runs the selection
    assert k2["reference_check"]["prompt_tokens"] > mcfg.index_topk
    assert k2["reference_check"]["prompt_tokens"] in s["engine"][
        "prefill_buckets"]


def test_parameter_counts_are_the_issues(k2):
    # ISSUE 42: q 8.39 M, k and v 2.10 M, o 8.39 M: 18.87 M
    assert entry.attention_params(k2) == 2 * 2048 * 4096 + 2 * 2048 * 512 \
        == 18_874_368
    assert entry.indexer_int8_params(k2) == 2048 * 1024 == 2_097_152
    # router 0.26 M, index key 0.13 M, head weights 0.03 M, bf16
    assert entry.small_bytes(k2) == 2 * 12 * 2048 * (128 + 64 + 16)
    assert entry.expert_params(k2) == 3 * 2048 * 768 == 4_718_592
    assert entry.head_params(k2) == 2048 * 151936 == 311_164_928
    layer = 18_874_368 + 2_097_152 + 2048 * (128 + 64 + 16) \
        + 128 * 4_718_592
    assert layer == pytest.approx(625.4e6, rel=1e-3)
    # twelve layers, the int8 head, the bf16 embedding: 8.44 GB
    assert 12 * layer + 311_164_928 * 3 == pytest.approx(8.44e9, rel=2e-3)
    # and the published 30B at 48 layers
    assert 48 * layer + 2 * 311_164_928 == pytest.approx(30.6e9, rel=1e-2)
    assert entry.kv_bytes_per_token_layer(k2) == 2 * 4 * (128 + 4) == 1056
    assert entry.index_bytes_per_token_layer(k2) == 128
    # 14,208 B a cached token, 1.82 MB a page, 4.89 GB a pool
    assert 12 * (1056 + 128) == 14_208
    assert 2688 * 128 * 14_208 == pytest.approx(4.89e9, rel=1e-3)


def test_expected_experts_hit(k2):
    # 16 tokens: an expert is missed with probability (120/128)^16
    assert entry.experts_hit(k2, 16) == pytest.approx(
        128 * (1 - (120 / 128) ** 16))
    assert 82 < entry.experts_hit(k2, 16) < 83
    assert entry.experts_hit(k2, 1) == pytest.approx(8.0)
    assert entry.experts_hit(k2, 0) == 0


def test_kernel_work_functions_count_what_any_form_must_do(k2):
    att = entry.attention_kernel(k2, calls=12, batch=16, context=10_000)
    # 2,048 selected rows of 1,056 B, q in and o back
    assert att["bytes"] == pytest.approx(
        12 * 16 * (2048 * 1056 + 2 * 32 * 128 * 2))
    assert att["flops"] == pytest.approx(12 * 16 * 2048 * 4 * 32 * 128)
    assert att["bytes"] == pytest.approx(0.42e9, rel=2e-2)
    # within topk every cached row is read
    short = entry.attention_kernel(k2, 1, 16, 1000)
    assert short["bytes"] == pytest.approx(16 * (1000 * 1056 + 16384))
    index = entry.index_kernel(k2, calls=12, batch=16, context=10_000)
    assert index["bytes"] == pytest.approx(12 * 16 * 10_000 * (128 + 4))
    assert index["flops"] == pytest.approx(12 * 16 * 10_000 * 2 * 16 * 64)
    assert index["bytes"] == pytest.approx(0.25e9, rel=2e-2)
    gmm = entry.moe_kernel(k2, calls=24, batch=16)
    assert gmm["flops"] == pytest.approx(12 * 2 * 128 * 4_718_592)
    assert gmm["bytes"] == pytest.approx(
        12 * (entry.experts_hit(k2, 16) * 4_718_592
              + 128 * (2048 + 3 * 768 + 2048) * 2))
    # 82 of 128 experts a layer: 0.39 GB a layer
    assert gmm["bytes"] / 12 == pytest.approx(0.39e9, rel=2e-2)


def test_decode_step_is_the_sum_of_its_parts_and_memory_bound(k2):
    work = entry.decode_step(k2, batch=16, context=9_999)
    att = entry.attention_kernel(k2, 12, 16, 10_000)
    index = entry.index_kernel(k2, 12, 16, 10_000)
    weights = entry.always_read_params(k2) \
        + 12 * entry.experts_hit(k2, 16) * 4_718_592
    assert work["bytes"] == pytest.approx(
        weights + entry.small_bytes(k2) + att["bytes"] + index["bytes"])
    # ISSUE 42: a step reads 5.2 GB of weights
    assert weights == pytest.approx(5.2e9, rel=2e-2)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(7.2e-3, rel=3e-2)


def test_prefill_counts_a_rows_keys_and_its_selected_rows(k2):
    work = entry.prefill(k2, prompt_tokens=12_288, mean_prompt=12_288,
                         programs=1)
    assert work["bytes"] == pytest.approx(
        entry.always_read_params(k2) + entry.small_bytes(k2)
        + 12 * entry.experts_hit(k2, 12_288) * 4_718_592
        + 12_288 * 12 * (1056 + 128))
    body = entry.always_read_params(k2) - entry.head_params(k2)
    assert work["flops"] == pytest.approx(
        2 * 12_288 * body + 2 * 12_288 * 8 * 4_718_592 * 12
        + 12_288 * 6144 * 2 * 16 * 64 * 12
        + 12_288 * 2048 * 4 * 32 * 128 * 12 + 2 * 311_164_928)


def _ctx(trace, config, events):
    return {"trace": trace, "config": config, "chips": 1, "seconds": 45.0,
            "traffic": {"trace": {"start_s": 15.0, "seconds": 3.0}},
            "peaks": roofline.load_peaks(BENCH_DIR, "TPU v5 lite"),
            "engine": {"events": events,
                       "trace_open": {"decode_steps": 0, "busy_slots_acc": 0},
                       "trace_close": {"decode_steps": 2,
                                       "busy_slots_acc": 8}}}


def test_index_kernel_reader_takes_the_context_from_the_events():
    """data/tiny.xplane.pb: three executions of `decode_multi_step`, four
    `convolution_tanh_fusion` calls each; here that op plays the kernel.
    The context is the traced stretch's `sparse_select` events' mean, not
    the traffic file's."""
    trace = xplane.reduce(xplane.load(os.path.join(DATA, "tiny.xplane.pb")))
    config = tiny_file()
    events = [{"kind": 22, "t": 14.0, "a": 900.0, "b": 0.5},   # before
              {"kind": 22, "t": 15.5, "a": 100.0, "b": 0.16},
              {"kind": 22, "t": 17.5, "a": 120.0, "b": 0.13},
              {"kind": 19, "t": 16.0, "a": 7.0, "b": 1.0},     # moe_load
              {"kind": 22, "t": 18.5, "a": 900.0, "b": 0.1}]   # after
    ctx = _ctx(trace, config, events)
    assert trace_index_kernel.traced_context(ctx) == pytest.approx(110.0)
    args = ("decode_multi_step", "convolution_tanh_fusion")
    device_s = sum(s for k, s in trace["ops"].items()
                   if k == "decode_multi_step/convolution_tanh_fusion")
    work = entry.index_kernel(config, 12, 4.0, 110.0)
    want = 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
    assert trace_index_kernel.read(ctx, *args) == pytest.approx(want)
    # a program without the kernel or the event (every one before the
    # indexer), an entry without the function, no trace: nothing, no raise
    assert trace_index_kernel.read(ctx, "decode_multi_step",
                                   "sparse_index_scores") is None
    assert trace_index_kernel.read(_ctx(trace, config, []), *args) is None
    assert trace_index_kernel.read(dict(ctx, config=tiny.TINY), *args) is None
    assert trace_index_kernel.read(dict(ctx, trace=None), *args) is None
    # the window's median of b, and nothing without the event
    assert engine_sparse_select.read(ctx, "b") == pytest.approx(0.145)
    assert engine_sparse_select.read(_ctx(trace, config, events[3:4]),
                                     "b") is None


def test_the_cells_files_are_found_by_the_names_in_benchmark_json():
    bench = bench_run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-closed16", 1)
    spec = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert os.path.isfile(os.path.join(os.path.dirname(BENCH_DIR),
                                       spec["file"]))
    assert spec["reduced"] == ["num_hidden_layers"]
    from benchmark.harness import traffic as traffic_mod
    t = traffic_mod.load_traffic(BENCH_DIR, cell["traffic"])
    assert (t["kind"], t["clients"], t["requests"], t["ramp_s"]) == (
        "closed", 16, 16, 24.0)
    assert t["prompt_tokens"] == {"dist": "uniform", "lo": 4096, "hi": 12288}
    assert t["output_tokens"]["hi"] - t["output_tokens"]["lo"] == 1024
    # every seed serves the same sixteen prompts in another order
    a = traffic_mod.build_schedule(t, 1, 45.0, 1000)
    b = traffic_mod.build_schedule(t, 2**31 + 5, 45.0, 1000)
    lens = sorted(len(r["prompt_ids"]) for r in a["requests"])
    assert lens == sorted(len(r["prompt_ids"]) for r in b["requests"])
    assert len(lens) == 16 and 4096 <= lens[0] and lens[-1] <= 12288
    traced = {m["name"] for m in bench_run.cell_metrics(bench, CELL, True)}
    assert {"closed.index_kernel_share", "closed.index_kernel_roofline",
            "closed.select_share", "closed.sparse.attended_over_scored",
            "closed.attention_kernel_share",
            "closed.attention_kernel_roofline", "closed.moe_kernel_share",
            "closed.moe_kernel_roofline", "closed.step.decode_ms",
            "closed.decode_step_roofline", "closed.moe.load_max_over_mean",
            "closed.sched.occupancy", "setup.warmup_s",
            "cache.step_program_misses"} <= traced
    assert {"out_tokens_per_s", "setup_s"} == {
        m["name"] for m in bench_run.cell_metrics(bench, CELL, False)}
    # no slot retires inside this cell's window, so `slot_interval` finds
    # nothing to read there and the cell is on neither list
    assert not {n for n in traced if n.startswith("closed.slot.")}
    # the new metrics are the new cell's alone
    for m in bench["per_layer"]:
        if m["name"].startswith(("closed.index", "closed.select",
                                 "closed.sparse")):
            assert m["workloads"] == [CELL]


def test_tiny_cell_through_run_cell():
    bench = bench_run.load_benchmark()
    metrics = (bench_run.cell_metrics(bench, CELL, False)
               + bench_run.cell_metrics(bench, CELL, True))
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED, metrics,
        seed=2**31 + 42, seconds=3.0, trace=False, allow_cpu=True)
    json.dumps(out)
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_asked"] == out["checks"]["tokens_generated"]
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert 0 < out["metrics"]["closed.sched.occupancy"]["value"] <= 4
    assert out["metrics"]["closed.moe.load_max_over_mean"]["value"] >= 1.0
    # contexts of 8 to 32 tokens around a topk of 16
    assert 0 < out["metrics"]["closed.sparse.attended_over_scored"][
        "value"] <= 1.0


def test_the_dense_reference_reads_not_correct(monkeypatch):
    """The negative control through the comparison that decides
    `correct`: the reference with selection switched off disagrees with
    what is served past topk."""
    def dense(config, params, ids):
        return entry.reference_forward(config, params, ids, sparse=False)[0]

    monkeypatch.setattr(entry, "reference_logits", dense)
    bench = bench_run.load_benchmark()
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED,
        bench_run.cell_metrics(bench, CELL, False), seed=2**31 + 44,
        seconds=2.0, trace=False, allow_cpu=True)
    assert out["failed"] == 0
    assert not out["checks"]["reference"]["ok"] and not out["correct"]
