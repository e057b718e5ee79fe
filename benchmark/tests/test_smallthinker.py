"""The smallthinker entry: the configuration file against the catalog's
keys, its counts of a step's work against counts worked by hand for one
pipeline stage of SmallThinker-21BA3B-Instruct (ISSUE 44's bytes), the two
new readers on a recorded trace, the cell's files by the names in
BENCHMARK.json, and a tiny configuration of the same keys through
`run_cell` on the CPU (a rehearsal, never a measurement)."""

import json
import os

import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.architectures import smallthinker as entry
from benchmark.harness import roofline, xplane
from benchmark.readers import engine_window_cache, trace_window_kernel
from benchmark.tests import test_rehearsal as tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
CELL = "smallthinker-21b.longctx-closed64"
CONFIG = "smallthinker-21b-a3b-int8"


@pytest.fixture(scope="module")
def st():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")) as fh:
        return json.load(fh)


def tiny_file(**over):
    """The source's keys at a tiny size: one period of [global, window,
    window, window], 4/2 heads of 16, a window of 8 tokens over pages of
    4, 8 experts of which 2 a token."""
    file = {
        "architecture": "smallthinker",
        "model_name": "smallthinker_tiny", "hidden_size": 64,
        "moe_ffn_hidden_size": 32, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
        "sliding_window_size": 8, "rope_scaling": None, "rope_theta": 1.5e6,
        "rms_norm_eps": 1e-6, "vocab_size": 512,
        "max_position_embeddings": 128, "tie_word_embeddings": False,
        "serving": {"chips": 1, "dtype": "float32",
                    "quantize_weights": "int8", "kv_dtype": "int8",
                    "n_pages": 64,
                    "engine": {"max_batch_size": 4, "max_seq_len": 64,
                               "page_size": 4, "prefill_buckets": [16, 32],
                               "max_prefill_group": 1,
                               "decode_steps_per_dispatch": 2}},
        # past the window, so that the check's prefill releases pages
        "reference_check": {"prompt_tokens": 19, "new_tokens": 3,
                            "rel_tol": 0.05},
    }
    file.update(over)
    return file


def test_the_file_keeps_every_published_key_but_the_depth(st):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert st["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if st.get(k) != v)
    assert differs == sorted(st["reduced"]) == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    # three whole periods, cut from the front of the published layouts
    assert st["num_hidden_layers"] == 12
    for key in ("rope_layout", "sliding_window_layout"):
        assert st[key] == row["config"][key][:12] == [0, 1, 1, 1] * 3
        assert st["published"][key] == row["config"][key]
    assert st["published"]["num_hidden_layers"] == 52
    for reading in ("(a) router input", "(b) q and k", "(c) window boundary",
                    "(d) experts", "rotary", "gates", "secondary experts",
                    "weights", "tokenizer", "page pools"):
        assert reading in st["assumed"], reading
    assert "pipeline of four" in st["deployment"]
    assert architectures.load(st) is entry
    mcfg = entry.model_config(st)
    assert (mcfg.dim, mcfg.n_layers, mcfg.cache_rows, mcfg.n_heads,
            mcfg.n_kv_heads, mcfg.head_dim, mcfg.vocab_size) == (
        2560, 12, 12, 28, 4, 128, 151936)
    assert (mcfg.n_experts, mcfg.experts_held, mcfg.n_experts_per_tok,
            mcfg.moe_mlp_dim) == (64, 64, 6, 768)
    assert (mcfg.window, mcfg.rope_theta, mcfg.rms_eps,
            mcfg.max_seq_len) == (4096, 1.5e6, 1e-6, 16384)
    assert tuple(mcfg.window_rows) == (4096, 3, 9)
    assert entry.step_kernel_calls(st) == 12
    s = st["serving"]
    assert (s["n_pages"], s["engine"]["max_batch_size"],
            s["engine"]["max_seq_len"], s["engine"]["page_size"],
            s["engine"]["max_prefill_group"]) == (8448, 64, 16384, 128, 1)
    assert s["engine"]["max_seq_len"] == st["max_position_embeddings"]
    # the check that decides `correct` prefills past the window
    assert st["reference_check"]["prompt_tokens"] == 4608 > mcfg.window
    assert st["reference_check"]["prompt_tokens"] in s["engine"][
        "prefill_buckets"]
    with pytest.raises(ValueError, match="layouts"):
        entry.model_config(dict(st, rope_layout=[0, 1]))


def test_parameter_counts_are_the_issues(st):
    # ISSUE 44: q 2560 x 3584 + k, v 2 x 2560 x 512 + o 3584 x 2560 = 20.97 M
    assert entry.attention_params(st) == 2 * 2560 * 3584 + 2 * 2560 * 512 \
        == 20_971_520
    assert entry.router_bytes(st) == 2 * 12 * 2560 * 64
    assert entry.expert_params(st) == 3 * 2560 * 768 == 5_898_240
    assert entry.head_params(st) == 2560 * 151936 == 388_956_160
    layer = 20_971_520 + 2560 * 64 + 64 * 5_898_240
    assert layer == pytest.approx(398.6e6, rel=1e-3)
    # twelve layers, the int8 head, the bf16 embedding: 5.95 GB
    assert 12 * layer + 388_956_160 * 3 == pytest.approx(5.95e9, rel=2e-3)
    # and the published 21B at 52 layers
    assert 52 * layer + 2 * 388_956_160 == pytest.approx(21.5e9, rel=1e-2)
    assert entry.kv_bytes_per_token_layer(st) == 2 * 4 * (128 + 4) == 1056
    assert entry.rows_by_kind(st) == (3, 9)
    # one table for all twelve rows: 208 MB a sequence of 16k, 13.3 GB for
    # 64; two tables: 3 x 16,384 + 9 x 4,224 tokens, 92 MB and 5.9 GB
    assert 12 * 16384 * 1056 == pytest.approx(208e6, rel=5e-3)
    assert (3 * 16384 + 9 * 33 * 128) * 1056 == pytest.approx(92e6, rel=1e-2)


def test_expected_experts_hit(st):
    # 64 tokens of 6 pairs: an expert is missed with probability (58/64)^64
    assert entry.experts_hit(st, 64) == pytest.approx(
        64 * (1 - (58 / 64) ** 64))
    assert 63.8 < entry.experts_hit(st, 64) < 64
    assert entry.experts_hit(st, 1) == pytest.approx(6.0)
    assert entry.experts_hit(st, 0) == 0


def test_work_functions_count_a_window_row_at_the_window(st):
    # a sequence of 9,000 cached tokens: 3 x 9,000 + 9 x 4,096
    assert entry.cached_rows(st, 9000) == 3 * 9000 + 9 * 4096
    assert entry.cached_rows(st, 1000) == 12 * 1000  # inside the window
    # the window rows' calls by the pages they walked: 135,168 B a page
    work = entry.window_attention_pages(st, pages=1000, calls=9, batch=64)
    assert work["bytes"] == pytest.approx(
        1000 * 128 * 1056 + 9 * 64 * 2 * 28 * 128 * 2)
    assert work["flops"] == pytest.approx(1000 * 128 * 4 * 28 * 128)
    gmm = entry.moe_kernel(st, calls=24, batch=64)
    assert gmm["flops"] == pytest.approx(12 * 2 * 384 * 5_898_240)
    assert gmm["bytes"] == pytest.approx(
        12 * (entry.experts_hit(st, 64) * 5_898_240
              + 384 * (2560 + 3 * 768 + 2560) * 2))


def test_decode_step_is_the_sum_of_its_parts_and_memory_bound(st):
    work = entry.decode_step(st, batch=64, context=8_999)
    weights = entry.always_read_params(st) \
        + 12 * entry.experts_hit(st, 64) * 5_898_240
    cache = 64 * (3 * 9000 + 9 * 4096) * 1056
    assert work["bytes"] == pytest.approx(
        weights + entry.router_bytes(st) + cache
        + 64 * 12 * 2 * 28 * 128 * 2)
    # ISSUE 44: 5.2 GB of weights a step and 4.3 GB of cache at 9k, of
    # which the window rows are 2.5 GB
    assert weights == pytest.approx(5.2e9, rel=2e-2)
    assert cache == pytest.approx(4.3e9, rel=2e-2)
    assert 64 * 9 * 4096 * 1056 == pytest.approx(2.5e9, rel=1e-2)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(11.6e-3, rel=3e-2)


def test_prefill_counts_a_window_rows_keys_at_the_window(st):
    work = entry.prefill(st, prompt_tokens=8192, mean_prompt=8192,
                         programs=1)
    assert work["bytes"] == pytest.approx(
        entry.always_read_params(st) + entry.router_bytes(st)
        + 12 * entry.experts_hit(st, 8192) * 5_898_240
        + 8192 * 12 * 1056)
    body = entry.always_read_params(st) - entry.head_params(st)
    assert work["flops"] == pytest.approx(
        2 * 8192 * body + 2 * 8192 * 6 * 5_898_240 * 12
        + 8192 * (3 * 4096 + 9 * 4096) * 4 * 28 * 128 + 2 * 388_956_160)


def _ctx(trace, config, events):
    return {"trace": trace, "config": config, "chips": 1, "seconds": 45.0,
            "traffic": {"trace": {"start_s": 15.0, "seconds": 3.0}},
            "peaks": roofline.load_peaks(BENCH_DIR, "TPU v5 lite"),
            "engine": {"events": events,
                       "trace_open": {"decode_steps": 0, "busy_slots_acc": 0},
                       "trace_close": {"decode_steps": 2,
                                       "busy_slots_acc": 8}}}


def _event(t, a, b, pages, calls):
    return {"kind": 23, "t": t, "a": a, "b": b,
            "aux": f"window_pages={pages} calls={calls}"}


def test_window_kernel_reader_takes_the_pages_from_the_events():
    """data/tiny.xplane.pb: three executions of `decode_multi_step`, four
    `convolution_tanh_fusion` calls each; here that op plays the kernel.
    The pages are those of the traced stretch's `window_cache` events, a
    call, times the calls the trace holds."""
    trace = xplane.reduce(xplane.load(os.path.join(DATA, "tiny.xplane.pb")))
    config = tiny_file()
    events = [_event(14.0, 0.9, 0.9, 9000, 6),                 # before
              _event(15.5, 0.60, 0.50, 120, 6),
              _event(17.5, 0.50, 0.40, 60, 3),
              {"kind": 19, "t": 16.0, "a": 7.0, "b": 1.0, "aux": ""},
              _event(18.5, 0.1, 0.1, 9000, 6)]                 # after
    ctx = _ctx(trace, config, events)
    assert trace_window_kernel.traced_pages_per_call(ctx) \
        == pytest.approx(180 / 9)
    args = ("decode_multi_step", "convolution_tanh_fusion")
    device_s = sum(s for k, s in trace["ops"].items()
                   if k == "decode_multi_step/convolution_tanh_fusion")
    work = entry.window_attention_pages(config, 20.0 * 12, 12, 4.0)
    want = 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
    assert trace_window_kernel.read(ctx, *args) == pytest.approx(want)
    # a program without the kernel or the event (every one before window
    # rows), an entry without the function, no trace: nothing, no raise
    assert trace_window_kernel.read(ctx, "decode_multi_step",
                                    "paged_attention_int8_window") is None
    assert trace_window_kernel.read(_ctx(trace, config, []), *args) is None
    assert trace_window_kernel.read(dict(ctx, config=tiny.TINY),
                                    *args) is None
    assert trace_window_kernel.read(dict(ctx, trace=None), *args) is None
    # the window's medians of a and b, and nothing without the event
    assert engine_window_cache.read(ctx, "a") == pytest.approx(0.55)
    assert engine_window_cache.read(ctx, "b") == pytest.approx(0.45)
    assert engine_window_cache.read(_ctx(trace, config, events[3:4]),
                                    "b") is None


def test_the_cells_files_are_found_by_the_names_in_benchmark_json():
    bench = bench_run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longctx-closed64", 1)
    spec = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert os.path.isfile(os.path.join(os.path.dirname(BENCH_DIR),
                                       spec["file"]))
    assert spec["reduced"] == ["num_hidden_layers", "sliding_window_layout",
                               "rope_layout"]
    from benchmark.harness import traffic as traffic_mod
    t = traffic_mod.load_traffic(BENCH_DIR, cell["traffic"])
    assert (t["kind"], t["clients"], t["requests"]) == ("closed", 64, 64)
    assert t["ramp_s"] >= 24.0 and t["ramp_s"] == int(t["ramp_s"])
    assert t["prompt_tokens"] == {"dist": "uniform", "lo": 4096, "hi": 8192}
    assert t["output_tokens"] == {"dist": "uniform", "lo": 7168, "hi": 8192}
    # every seed serves the same 64 prompts in another order, and prompt
    # plus answer stays inside the published context
    a = traffic_mod.build_schedule(t, 1, 45.0, 1000)
    b = traffic_mod.build_schedule(t, 2**31 + 5, 45.0, 1000)
    lens = sorted(len(r["prompt_ids"]) for r in a["requests"])
    assert lens == sorted(len(r["prompt_ids"]) for r in b["requests"])
    assert len(lens) == 64 and 4096 <= lens[0] and lens[-1] <= 8192
    assert max(len(r["prompt_ids"]) + r["max_tokens"]
               for r in a["requests"]) <= 16384
    traced = {m["name"] for m in bench_run.cell_metrics(bench, CELL, True)}
    assert traced == {
        "cache.step_program_misses", "closed.decode_step_roofline",
        "closed.sched.occupancy", "closed.step.decode_ms",
        "setup.reference_s", "setup.warmup_s", "setup.weights_s",
        "closed.window.decode_ms", "closed.window.device_busy_share",
        "closed.window.longest_program_ms", "closed.attention_kernel_share",
        "closed.moe_kernel_share", "closed.moe_kernel_roofline",
        "closed.moe.load_max_over_mean", "closed.window_attn_kernel_share",
        "closed.window_attn_kernel_roofline",
        "closed.attn.rows_walked_over_context",
        "closed.cache.pages_held_over_one_table"}
    assert {"out_tokens_per_s", "setup_s"} == {
        m["name"] for m in bench_run.cell_metrics(bench, CELL, False)}
    # the new metrics are the new cell's alone
    for m in bench["per_layer"]:
        if m["name"] in ("closed.window_attn_kernel_share",
                         "closed.window_attn_kernel_roofline",
                         "closed.attn.rows_walked_over_context",
                         "closed.cache.pages_held_over_one_table"):
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
    # contexts of 8 to 32 tokens around a window of 8: three of four
    # layers see less than the context, and hold fewer pages for it
    assert 0.25 < out["metrics"]["closed.attn.rows_walked_over_context"][
        "value"] < 1.0
    assert 0.25 < out["metrics"]["closed.cache.pages_held_over_one_table"][
        "value"] < 1.0


@pytest.mark.parametrize("control", [
    dict(windowed=False), dict(router_reads="ffn")],
    ids=["no-window", "router-after-attention"])
def test_a_reference_of_another_model_reads_not_correct(monkeypatch, control):
    """The negative controls through the comparison that decides
    `correct`: the reference with no window, or with the router on the
    feed-forward's input, disagrees with what is served. (Three greedy
    tokens at 5 % do not tell silu from relu at this size: that control is
    held on the logits, tests/test_window_attn_moe.py.)"""
    def other(config, params, ids):
        return entry.reference_forward(config, params, ids, **control)[0]

    monkeypatch.setattr(entry, "reference_logits", other)
    bench = bench_run.load_benchmark()
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED,
        bench_run.cell_metrics(bench, CELL, False), seed=2**31 + 44,
        seconds=2.0, trace=False, allow_cpu=True)
    assert out["failed"] == 0
    assert not out["checks"]["reference"]["ok"] and not out["correct"]
