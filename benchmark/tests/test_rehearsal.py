"""Each cell's code path end to end at a tiny size on the CPU. The tiny
sizes live here, not in benchmark/configs/. A rehearsal proves control
flow and accounting; it is never a measurement."""

import copy
import json
import os

import pytest

from benchmark import run as bench_run

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "model_type": "mistral", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "max_position_embeddings": 256, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
    "serving": {"chips": 1, "dtype": "float32", "quantize_weights": "int8",
                "kv_dtype": "int8", "n_pages": 64,
                "engine": {"max_batch_size": 4, "max_seq_len": 256,
                           "page_size": 16, "prefill_buckets": [32, 128],
                           "max_prefill_group": 2,
                           "decode_steps_per_dispatch": 2}},
    "reference_check": {"prompt_tokens": 12, "new_tokens": 3, "rel_tol": 0.05},
}
CLOSED = {"kind": "closed", "clients": 4, "requests": 16, "ramp_s": 0.5,
          "endpoint": "completions",
          "prompt_tokens": {"dist": "uniform", "lo": 8, "hi": 24},
          "output_tokens": {"dist": "uniform", "lo": 4, "hi": 8}}
OPEN = {"kind": "open", "rate_per_s": 4.0, "ramp_s": 0.5,
        "endpoint": "completions",
        "prompt_tokens": {"dist": "bounded_pareto", "lo": 8, "hi": 100,
                          "alpha": 1.0},
        "output_tokens": {"dist": "bounded_pareto", "lo": 3, "hi": 12,
                          "alpha": 1.2},
        "slo": {"ttft_ms": 60000, "mean_gap_ms": 60000}}
CHAIN = {"kind": "open", "rate_per_s": 2.0, "ramp_s": 0.5,
         "endpoint": "chain_generate",
         "prompt_tokens": {"dist": "uniform", "lo": 4, "hi": 8},
         "output_tokens": {"dist": "uniform", "lo": 3, "hi": 6},
         "corpus": {"chunks": 16, "chunk_tokens": 10, "files": 2},
         "slo": {"ttft_ms": 60000, "mean_gap_ms": 60000}}


def _metrics(cell_name):
    bench = bench_run.load_benchmark()
    return (bench_run.cell_metrics(bench, cell_name, False)
            + bench_run.cell_metrics(bench, cell_name, True))


def _run(cell_name, config, traffic, chips=1, seconds=3.0, seed=2**31 + 11):
    cell = {"name": cell_name, "chips": chips}
    out = bench_run.run_cell(cell, config, traffic, _metrics(cell_name),
                             seed=seed, seconds=seconds, trace=False,
                             allow_cpu=True)
    json.dumps(out)  # the printed line must serialise
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_asked"] == out["checks"]["tokens_generated"]
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips
    assert out["metrics"]["setup_s"]["value"] > 0
    return out


def test_closed_cell():
    out = _run("mistral7b.decode-closed64", TINY, CLOSED)
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert 0 < out["metrics"]["closed.sched.occupancy"]["value"] <= 4


def test_open_cell():
    out = _run("mistral7b.chat-open", TINY, OPEN)
    for name in ("gap_p50_ms", "gap_p99_ms", "ttft_p50_ms",
                 "chat.ttft_p90_ms", "loadgen.lag_p95_ms",
                 "sched.queue_wait_p50_ms", "surface.overhead_p50_ms",
                 "slo.attained_share"):
        assert name in out["metrics"], (name, out["metrics"])
    assert out["checks"]["requests_due_in_window"] == 12  # 4/s x 3 s
    assert out["attempted"] == 14  # plus 4/s x 0.5 s of ramp


def test_chain_cell():
    config = copy.deepcopy(TINY)
    config["serving"]["engine"]["prefill_buckets"] = [128]
    config["encoders"] = {"embedder": {
        "geometry": "tiny", "dtype": "float32",
        "overrides": {"vocab_size": 512},
        "engine": {"max_batch": 4, "buckets": [32, 64]}}}
    with open(os.path.join(BENCH_DIR, "configs",
                           "rag-arctic-l-mistral-7b.json")) as fh:
        env = json.load(fh)["chain"]["env"]
    config["chain"] = {"env": dict(
        env, APP_EMBEDDINGS_DIMENSIONS="32", APP_TEXTSPLITTER_CHUNKSIZE="12",
        APP_RETRIEVER_MAXCONTEXTTOKENS="40")}
    out = _run("rag.chain-open", config, CHAIN)
    assert "chain.pre_llm_p50_ms" in out["metrics"]
    assert out["metrics"]["chain.pre_llm_p50_ms"]["value"] > 0


def test_tp_cell_on_four_virtual_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    config = copy.deepcopy(TINY)
    config["serving"]["chips"] = 4
    config["num_key_value_heads"] = 4
    out = _run("mistral-small-24b-tp4.decode-closed64", config, CLOSED,
               chips=4)
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0


def test_no_tpu_is_an_error():
    from benchmark.harness import system

    with pytest.raises(SystemExit):
        system.require_devices(1, allow_cpu=False)
