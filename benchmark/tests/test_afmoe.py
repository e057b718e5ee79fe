"""The afmoe entry: the configuration file against the catalog's keys, its
counts of a step's work against counts worked by hand for one chip's
share of Trinity-Large-Preview (ISSUE 52's bytes), the three new readers
on a recorded trace, the cell's files by the names in BENCHMARK.json, and
a tiny configuration of the same keys through `run_cell` on the CPU (a
rehearsal, never a measurement)."""

import json
import os

import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.architectures import afmoe as entry
from benchmark.harness import roofline, xplane
from benchmark.readers import (
    engine_moe_hit, engine_window_cache, trace_global_kernel,
    trace_moe_hit_kernel, trace_window_kernel)
from benchmark.tests import test_rehearsal as tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
CELL = "trinity-large-ep8.longctx-closed32"
CONFIG = "trinity-large-preview-int8-ep8"
SIBLING = "smallthinker-21b.longctx-closed64"
NEW = ("closed.moe.experts_hit_share", "closed.moe_hit_kernel_roofline",
       "closed.global_attn_kernel_roofline")


@pytest.fixture(scope="module")
def tr():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")) as fh:
        return json.load(fh)


def tiny_file(**over):
    """The source's keys at a tiny size: a dense sliding layer, then one
    period of [sliding, sliding, sliding, full] of expert layers, 4/2
    heads of 16, a window of 8 tokens over pages of 4, a router of 16
    outputs and 4 a token of which experts 4..7 are held."""
    file = {
        "architecture": "afmoe", "model_type": "afmoe", "hidden_size": 64,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_hidden_layers": 5, "num_dense_layers": 1,
        "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 4, "expert_offset": 4, "num_experts_per_tok": 4,
        "num_shared_experts": 1, "route_norm": True, "route_scale": 2.448,
        "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "mup_enabled": True, "hidden_act": "silu", "sliding_window": 8,
        "rope_scaling": None, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "vocab_size": 512, "max_position_embeddings": 128,
        "tie_word_embeddings": False,
        "published": {"num_experts": 16},
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


def test_the_file_keeps_every_published_key_but_the_cut(tr):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "Trinity-Large-Preview")
    pub = row["config"]
    assert tr["source"] == row["source_url"]
    differs = sorted(k for k, v in pub.items() if tr.get(k) != v)
    assert differs == sorted(tr["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert tr["published"] == {k: pub[k] for k in tr["reduced"]}
    # the dense layer once (published layer 0) and published 8-15
    assert tr["layer_types"] == pub["layer_types"][:1] \
        + pub["layer_types"][8:16]
    assert entry.layer_kinds(tr) == (1, 1, 1, 1, 0, 1, 1, 1, 0)
    assert (tr["num_hidden_layers"], tr["num_dense_layers"],
            tr["num_experts"], tr["vocab_size"]) == (9, 1, 32, 25024)
    assert tr["vocab_size"] * 8 == pub["vocab_size"]
    for reading in ("(a) the gate", "(b) q/k norm", "(c) rotation",
                    "(d) window boundary", "(e) the four norms",
                    "(f) embedding multiplier", "(g) router",
                    "the share", "weights", "tokenizer", "page pools"):
        assert reading in tr["assumed"], reading
    assert "8-chip expert-parallel group" in tr["deployment"]
    assert architectures.load(tr) is entry
    mcfg = entry.model_config(tr)
    assert (mcfg.dim, mcfg.n_layers, mcfg.n_dense_layers, mcfg.n_heads,
            mcfg.n_kv_heads, mcfg.head_dim, mcfg.vocab_size) == (
        3072, 9, 1, 48, 8, 128, 25024)
    assert (mcfg.n_routed_experts, mcfg.experts_held, mcfg.expert_offset,
            mcfg.n_experts_per_tok, mcfg.moe_mlp_dim, mcfg.mlp_dim) == (
        256, 32, 0, 4, 3072, 12288)
    assert (mcfg.window, mcfg.rope_theta, mcfg.rms_eps, mcfg.max_seq_len,
            mcfg.routed_scaling_factor) == (4096, 1e4, 1e-5, 262144, 2.448)
    assert mcfg.embed_scale == pytest.approx(3072 ** 0.5)
    assert mcfg.init_depth == 60  # the published depth scales the gains
    assert tuple(mcfg.window_rows) == (4096, 2, 7)
    assert entry.step_kernel_calls(tr) == 9
    s = tr["serving"]
    assert (s["n_pages"], s["engine"]["max_batch_size"],
            s["engine"]["max_seq_len"], s["engine"]["page_size"],
            s["engine"]["max_prefill_group"]) == (7232, 32, 28672, 128, 1)
    assert s["n_pages"] == 32 * (28672 // 128) + 64
    buckets = s["engine"]["prefill_buckets"]
    assert buckets[0] == 4608 and len(buckets) <= 7
    # the check that decides `correct` prefills past the window
    assert tr["reference_check"]["prompt_tokens"] == 4608 > mcfg.window
    with pytest.raises(ValueError, match="layer_types"):
        entry.model_config(dict(tr, layer_types=["full_attention"]))
    with pytest.raises(ValueError, match="sigmoid"):
        entry.model_config(dict(tr, score_func="softmax"))


def test_parameter_counts_are_the_issues(tr):
    # ISSUE 52: attention 3 x 18.87 M (q, g, o) + 2 x 3.15 M = 62.9 M
    assert entry.attention_params(tr) == 3 * 3072 * 6144 + 2 * 3072 * 1024 \
        == 62_914_560
    assert entry.dense_ffn_params(tr) == 3 * 3072 * 12288 == 113_246_208
    assert entry.expert_params(tr) == 3 * 3072 * 3072 == 28_311_552
    assert entry.router_bytes(tr) == 8 * 256 * (2 * 3072 + 4)
    assert entry.head_params(tr) == 3072 * 25024
    layer = 62_914_560 + 28_311_552 + 3072 * 256 + 32 * 28_311_552
    assert layer == pytest.approx(998e6, rel=1e-3)
    dense = 62_914_560 + 113_246_208
    # the dense layer, eight expert layers, the int8 head, the bf16
    # embedding: 8.4 GB
    assert dense + 8 * layer + 3 * 3072 * 25024 == pytest.approx(
        8.4e9, rel=1e-2)
    # whole, one expert layer is 7.3 GB; the published 400B
    whole = 62_914_560 + 28_311_552 + 3072 * 256 + 256 * 28_311_552
    assert whole == pytest.approx(7.3e9, rel=1e-2)
    assert 6 * dense + 54 * whole + 2 * 3072 * 200192 == pytest.approx(
        398e9, rel=1e-2)
    assert entry.kv_bytes_per_token_layer(tr) == 2 * 8 * (128 + 4) == 2112
    assert entry.rows_by_kind(tr) == (2, 7)
    # one table for all nine rows: 17.4 GB for 32 sequences of 28,672
    assert 32 * 28672 * 9 * 2112 == pytest.approx(17.4e9, rel=5e-3)
    assert 7232 * 128 * 2 * 2112 == pytest.approx(3.91e9, rel=5e-3)
    assert (33 * 34 + 1) * 128 * 7 * 2112 == pytest.approx(2.1e9, rel=2e-2)


def test_expected_experts_hit(tr):
    # 32 tokens of 4 pairs over 256: a held expert is missed with
    # probability (63/64)^32; two of five are hit
    assert entry.experts_hit(tr, 32) == pytest.approx(
        32 * (1 - (63 / 64) ** 32))
    assert entry.experts_hit(tr, 32) / 32 == pytest.approx(0.396, abs=1e-3)
    assert entry.experts_hit(tr, 256) / 32 == pytest.approx(0.98, abs=3e-3)
    assert entry.experts_hit(tr, 0) == 0
    assert entry.local_share(tr) == 1 / 8


def test_work_functions(tr):
    # a sequence of 16,000 cached tokens: 2 x 16,000 + 7 x 4,096
    assert entry.cached_rows(tr, 16000) == 2 * 16000 + 7 * 4096
    assert entry.cached_rows(tr, 1000) == 9 * 1000  # inside the window
    # either group's calls by the pages they walked: 270,336 B a page
    for fn in (entry.window_attention_pages, entry.global_attention_pages):
        work = fn(tr, pages=1000, calls=7, batch=32)
        assert work["bytes"] == pytest.approx(
            1000 * 128 * 2112 + 7 * 32 * 2 * 48 * 128 * 2)
        assert work["flops"] == pytest.approx(1000 * 128 * 4 * 48 * 128)
    rows = (32 * 4 / 8) * (3072 + 3 * 3072 + 3072) * 2
    gmm = entry.moe_kernel(tr, calls=16, batch=32)
    assert gmm["flops"] == pytest.approx(8 * 2 * 16 * 28_311_552)
    assert gmm["bytes"] == pytest.approx(
        8 * (entry.experts_hit(tr, 32) * 28_311_552 + rows))
    # by the experts the blocks DID hit: a quarter of the 32 held
    hit = entry.moe_kernel_hit(tr, calls=16, hit_share=0.25, batch=32)
    assert hit["flops"] == gmm["flops"]
    assert hit["bytes"] == pytest.approx(8 * (8 * 28_311_552 + rows))
    at_expected = entry.moe_kernel_hit(
        tr, 16, entry.experts_hit(tr, 32) / 32, 32)
    assert at_expected["bytes"] == pytest.approx(gmm["bytes"])
    attn = entry.attention_kernel(tr, calls=18, batch=32, context=16000)
    assert attn["bytes"] == pytest.approx(
        2 * 32 * (2 * 16000 + 7 * 4096) * 2112
        + 18 * 32 * 2 * 48 * 128 * 2)


def test_decode_step_is_the_sum_of_its_parts_and_memory_bound(tr):
    work = entry.decode_step(tr, batch=32, context=15_999)
    routed = 8 * entry.experts_hit(tr, 32) * 28_311_552
    other = entry.always_read_params(tr)
    glob, win = 32 * 2 * 16000 * 2112, 32 * 7 * 4096 * 2112
    assert work["bytes"] == pytest.approx(
        other + routed + entry.router_bytes(tr) + glob + win
        + 32 * 9 * 2 * 48 * 128 * 2)
    # ISSUE 52's four parts: 2.9 GB of routed experts, 1.0 GB of other
    # weights, 2.4 GB of global rows at contexts of 9k-24k (2.2 at 16k),
    # 2.0 GB of window rows (1.94)
    assert routed == pytest.approx(2.9e9, rel=2e-2)
    assert other == pytest.approx(1.0e9, rel=2e-2)
    assert glob == pytest.approx(2.16e9, rel=1e-2)
    assert win == pytest.approx(1.94e9, rel=1e-2)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(9.8e-3, rel=3e-2)


def test_prefill_counts_a_sliding_rows_keys_at_the_window(tr):
    work = entry.prefill(tr, prompt_tokens=20480, mean_prompt=20480,
                         programs=1)
    assert work["bytes"] == pytest.approx(
        entry.always_read_params(tr) + entry.router_bytes(tr)
        + 8 * 32 * 28_311_552 + 20480 * 9 * 2112, rel=1e-6)
    body = entry.always_read_params(tr) - entry.head_params(tr)
    assert work["flops"] == pytest.approx(
        2 * 20480 * body + 2 * 20480 * 4 / 8 * 28_311_552 * 8
        + 20480 * (2 * 10240 + 7 * 4096) * 4 * 48 * 128
        + 2 * 3072 * 25024)


# -- the three new readers ----------------------------------------------------

def _ctx(trace, config, events):
    return {"trace": trace, "config": config, "chips": 1, "seconds": 45.0,
            "traffic": {"trace": {"start_s": 15.0, "seconds": 3.0}},
            "peaks": roofline.load_peaks(BENCH_DIR, "TPU v5 lite"),
            "engine": {"events": events,
                       "trace_open": {"decode_steps": 0, "busy_slots_acc": 0},
                       "trace_close": {"decode_steps": 2,
                                       "busy_slots_acc": 8}}}


def _window(t, pages, calls, gpages, gcalls):
    return {"kind": 23, "t": t, "a": 0.5, "b": 0.4,
            "aux": f"window_pages={pages} calls={calls} updates={pages} "
                   f"global_pages={gpages} global_calls={gcalls}"}


def _load(t, hit, of, aux=True):
    return {"kind": 19, "t": t, "a": 7.0, "b": 2.0,
            "aux": f"hit={hit} of={of}" if aux else ""}


EVENTS = [_window(14.0, 9000, 6, 9000, 2), _load(14.0, 64, 64),  # before
          _window(15.5, 120, 6, 300, 2), _load(15.5, 10, 64),
          _window(17.5, 60, 3, 200, 2), _load(17.5, 22, 64),
          _window(18.5, 9000, 6, 9000, 2), _load(18.5, 60, 64)]  # after
# data/tiny.xplane.pb: three executions of `decode_multi_step`, four
# `convolution_tanh_fusion` calls each; here that op plays the kernel
ARGS = ("decode_multi_step", "convolution_tanh_fusion")


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce(xplane.load(os.path.join(DATA, "tiny.xplane.pb")))


def _device_s(trace):
    return sum(s for k, s in trace["ops"].items()
               if k == "decode_multi_step/convolution_tanh_fusion")


def test_the_hit_share_is_hit_over_of_of_the_windows_events(trace):
    ctx = _ctx(trace, tiny_file(), EVENTS)
    assert engine_moe_hit.read(ctx) == pytest.approx(156 / 256)
    # events without the keys (every program before them), or none: None
    old = [_load(16.0, 0, 0, aux=False), _window(16.0, 10, 3, 10, 1)]
    assert engine_moe_hit.read(_ctx(trace, tiny_file(), old)) is None
    assert engine_moe_hit.read(_ctx(trace, tiny_file(), [])) is None


def test_the_hit_kernel_reader_takes_the_experts_from_the_events(trace):
    """The hit share is the traced stretch's events' (32 of 128), the
    calls are the trace's."""
    config = tiny_file()
    ctx = _ctx(trace, config, EVENTS)
    work = entry.moe_kernel_hit(config, 12, 32 / 128, 4.0)
    want = 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / _device_s(trace)
    assert trace_moe_hit_kernel.read(ctx, *ARGS) == pytest.approx(want)
    # a program without the kernel, events without `hit`, an entry
    # without the function, no trace: nothing, and no raise
    assert trace_moe_hit_kernel.read(ctx, "decode_multi_step",
                                     "moe_grouped_matmul") is None
    old = [dict(e, aux="") if e["kind"] == 19 else e for e in EVENTS]
    assert trace_moe_hit_kernel.read(_ctx(trace, config, old),
                                     *ARGS) is None
    assert trace_moe_hit_kernel.read(dict(ctx, config=tiny.TINY),
                                     *ARGS) is None
    assert trace_moe_hit_kernel.read(dict(ctx, trace=None), *ARGS) is None


def test_the_global_kernel_reader_takes_the_pages_from_the_events(trace):
    config = tiny_file()
    ctx = _ctx(trace, config, EVENTS)
    assert trace_global_kernel.traced_pages_per_call(ctx) \
        == pytest.approx(500 / 4)
    work = entry.global_attention_pages(config, 125.0 * 12, 12, 4.0)
    want = 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / _device_s(trace)
    assert trace_global_kernel.read(ctx, *ARGS, exclude="_window") \
        == pytest.approx(want)
    # the calls whose name holds `exclude` are not the global rows'
    assert trace_global_kernel.read(ctx, *ARGS, exclude="tanh") is None
    # window_cache events from before the keys (SmallThinker's on the
    # parent), an entry without the function, no trace: nothing, no raise
    old = [dict(e, aux="window_pages=60 calls=3 updates=60")
           if e["kind"] == 23 else e for e in EVENTS]
    assert trace_global_kernel.read(_ctx(trace, config, old), *ARGS,
                                    exclude="_window") is None
    assert trace_global_kernel.read(dict(ctx, config=tiny.TINY), *ARGS,
                                    exclude="_window") is None
    assert trace_global_kernel.read(dict(ctx, trace=None), *ARGS,
                                    exclude="_window") is None


def test_the_accepted_readers_ignore_the_new_keys(trace):
    """`window_pages=<n> calls=<m>` are read as before beside the two new
    keys, and the median of `moe_load`'s b beside its new `aux`."""
    from benchmark.readers import engine_flight_median
    ctx = _ctx(trace, tiny_file(), EVENTS)
    assert trace_window_kernel.traced_pages_per_call(ctx) \
        == pytest.approx(180 / 9)
    assert trace_window_kernel.read(ctx, *ARGS) is not None
    assert engine_window_cache.read(ctx, "b") == pytest.approx(0.4)
    assert engine_flight_median.read(ctx, "moe_load", "b") == 2.0


# -- the cell -----------------------------------------------------------------

def test_the_cells_files_are_found_by_the_names_in_benchmark_json():
    bench = bench_run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longctx-closed32", 1)
    assert "attention over its share" in cell["why"] \
        and "9 layers" in cell["why"]
    spec = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert os.path.isfile(os.path.join(os.path.dirname(BENCH_DIR),
                                       spec["file"]))
    assert spec["reduced"] == ["num_hidden_layers", "layer_types",
                               "num_dense_layers", "num_experts",
                               "vocab_size"]
    from benchmark.harness import traffic as traffic_mod
    t = traffic_mod.load_traffic(BENCH_DIR, cell["traffic"])
    assert (t["kind"], t["clients"], t["requests"], t["endpoint"]) == (
        "closed", 32, 32, "completions")
    assert t["ramp_s"] % 4 == 0 and t["trace"] == {"start_s": 15.0,
                                                   "seconds": 3.0}
    assert t["prompt_tokens"] == {"dist": "uniform", "lo": 8192,
                                  "hi": 20480}
    assert t["output_tokens"] == {"dist": "uniform", "lo": 7680,
                                  "hi": 8192}
    # every seed serves the same 32 prompts in another order, drawn from
    # the held slice, and prompt plus answer stays inside a slot's table
    a = traffic_mod.build_schedule(t, 1, 45.0, 25024)
    b = traffic_mod.build_schedule(t, 2**31 + 5, 45.0, 25024)
    lens = sorted(len(r["prompt_ids"]) for r in a["requests"])
    assert lens == sorted(len(r["prompt_ids"]) for r in b["requests"])
    assert len(lens) == 32 and 8192 <= lens[0] and lens[-1] <= 20480
    assert sum(lens) == pytest.approx(459e3, rel=1e-2)
    assert max(len(r["prompt_ids"]) + r["max_tokens"]
               for r in a["requests"]) <= 28672
    assert max(max(r["prompt_ids"]) for r in a["requests"]) < 25024
    traced = {m["name"] for m in bench_run.cell_metrics(bench, CELL, True)}
    sibling = {m["name"]
               for m in bench_run.cell_metrics(bench, SIBLING, True)}
    # SmallThinker's lists but the roofline that counts EXPECTED experts,
    # and in its place the three that count what ran
    assert traced == (sibling - {"closed.moe_kernel_roofline"}) | set(NEW)
    assert {"out_tokens_per_s", "setup_s"} == {
        m["name"] for m in bench_run.cell_metrics(bench, CELL, False)}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            with open(os.path.join(BENCH_DIR, "metrics",
                                   m["name"] + ".json")) as fh:
                file = json.load(fh)
            assert {k: file[k] for k in ("layer", "unit", "better",
                                         "source", "moves")} \
                == {k: m[k] for k in ("layer", "unit", "better", "source",
                                      "moves")}


def test_tiny_cell_through_run_cell():
    bench = bench_run.load_benchmark()
    metrics = (bench_run.cell_metrics(bench, CELL, False)
               + bench_run.cell_metrics(bench, CELL, True))
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED, metrics,
        seed=2**31 + 52, seconds=3.0, trace=False, allow_cpu=True)
    json.dumps(out)
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_asked"] == out["checks"]["tokens_generated"]
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert 0 < out["metrics"]["closed.sched.occupancy"]["value"] <= 4
    assert out["metrics"]["closed.moe.load_max_over_mean"]["value"] >= 1.0
    assert 0.0 < out["metrics"]["closed.moe.experts_hit_share"]["value"] \
        <= 1.0
    # contexts of 8 to 32 tokens around a window of 8: four of five
    # layers see less than the context, and hold fewer pages for it
    assert 0.2 < out["metrics"]["closed.attn.rows_walked_over_context"][
        "value"] < 1.0
    assert 0.2 < out["metrics"]["closed.cache.pages_held_over_one_table"][
        "value"] < 1.0


@pytest.mark.parametrize("control", [
    dict(windowed=False), dict(post_norms=False)],
    ids=["no-window", "two-norms"])
def test_a_reference_of_another_model_reads_not_correct(monkeypatch, control):
    """The negative controls through the comparison that decides
    `correct`: the reference with no window, or with a norm before each
    branch only, disagrees with what is served. (Three greedy tokens at
    5 % do not tell the gate, the q/k norm or the selection's bias at
    this size: those are held on the logits,
    tests/test_gated_window_moe.py.)"""
    def other(config, params, ids):
        return entry.reference_forward(config, params, ids, **control)[0]

    monkeypatch.setattr(entry, "reference_logits", other)
    bench = bench_run.load_benchmark()
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED,
        bench_run.cell_metrics(bench, CELL, False), seed=2**31 + 54,
        seconds=2.0, trace=False, allow_cpu=True)
    assert out["failed"] == 0
    assert not out["checks"]["reference"]["ok"] and not out["correct"]
