"""The xing4 entry: the configuration file against the catalog's keys, its
reference against the program at a tiny size (logits, not tokens), its
counts of a step's work against counts worked by hand at the published
widths, the cell's files found by the names in BENCHMARK.json, and a tiny
configuration of the same keys through `run_cell` on the CPU (a
rehearsal, never a measurement)."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.architectures import axk1
from benchmark.architectures import xing4 as entry
from benchmark.harness import roofline
from benchmark.tests import test_rehearsal as tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "xing4-29b-ep4.chat-closed128"
CONFIG = "xing4.0-29b-a4b-int8-ep4"


@pytest.fixture(scope="module")
def x4():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")) as fh:
        return json.load(fh)


def tiny_file(**over):
    """Xing4.0's keys at a tiny size: 4 layers (two dense), 16 experts of
    which 4 are held from expert 4 on, 4 a token, `hc_mult` streams."""
    c = {
        "architecture": "xing4", "model_type": "xing4_0", "hidden_size": 64,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_hidden_layers": 4, "first_k_dense_replace": 2,
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": 4, "expert_offset": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "num_nextn_predict_layers": 1, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "vocab_size": 512, "max_position_embeddings": 256,
        "rope_theta": 10000, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False,
        "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 32},
        "published": {"n_routed_experts": 16},
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
    c.update(over)
    return c


def test_the_file_keeps_every_published_key_but_the_experts_held(x4):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    assert x4["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if x4.get(k) != v)
    assert differs == x4["reduced"] == ["n_routed_experts"]
    assert x4["published"] == {"n_routed_experts": 64}
    assert (x4["n_routed_experts"], x4["expert_offset"]) == (16, 0)
    assert "4-chip expert-parallel host" in x4["deployment"]
    assumed = x4["assumed"]
    assert "NOT read" in assumed["num_nextn_predict_layers"]
    for key in ("equations", "the mixing's norm", "streams in and out",
                "the branch's norm", "mixing weights", "rope pair layout",
                "yarn", "biases", "weights",
                "serving.engine.max_batch_size"):
        assert key in assumed, key
    assert sum("hold it against the source" in v or "hold every one" in v
               for v in assumed.values()) >= 5
    assert architectures.load(x4) is entry
    mcfg = entry.model_config(x4)
    assert (mcfg.dim, mcfg.n_layers, mcfg.n_dense_layers, mcfg.n_heads,
            mcfg.q_lora_rank, mcfg.latent_row, mcfg.qk_nope_head_dim,
            mcfg.v_head_dim, mcfg.mlp_dim, mcfg.moe_mlp_dim) == (
        3584, 40, 2, 32, 768, (512, 64), 128, 128, 9216, 1024)
    assert (mcfg.n_routed_experts, mcfg.n_experts_per_tok, mcfg.experts_held,
            mcfg.expert_offset, mcfg.vocab_size) == (64, 4, 16, 0, 131072)
    assert (mcfg.hc_mult, mcfg.hc_sinkhorn_iters, mcfg.hc_eps,
            mcfg.hc_res_clamp, mcfg.router_bias) == (
        4, 20, 1e-6, (-30.0, 30.0), True)
    assert mcfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.41589 ** 2,
                                               rel=1e-4)
    assert entry.step_kernel_calls(x4) == 40
    e = x4["serving"]["engine"]
    assert (e["max_batch_size"], e["max_seq_len"], e["page_size"],
            e["prefill_buckets"], e["max_prefill_group"],
            x4["serving"]["n_pages"]) == (128, 640, 128, [128, 256], 4, 640)
    assert x4["reference_check"] == {"prompt_tokens": 48, "new_tokens": 4,
                                     "rel_tol": 0.05}


def test_the_cells_files_are_found_by_the_names_in_benchmark_json():
    bench = bench_run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-closed128", 1)
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf["reduced"] == ["n_routed_experts"]
    with open(os.path.join(BENCH_DIR, "traffic", "chat-closed128.json")) as fh:
        mix = json.load(fh)
    assert (mix["kind"], mix["clients"], mix["requests"], mix["ramp_s"],
            mix["base_seed"]) == ("closed", 128, 1024, 16.0, 57)
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 64, "hi": 192}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 320, "hi": 448}
    assert mix["trace"] == {"start_s": 5.0, "seconds": 3.0}
    names = {m["name"] for m in bench_run.cell_metrics(bench, CELL, False)
             + bench_run.cell_metrics(bench, CELL, True)}
    k1 = {m["name"] for m in
          bench_run.cell_metrics(bench, "ax-k1-ep16.decode-closed128", False)
          + bench_run.cell_metrics(bench, "ax-k1-ep16.decode-closed128",
                                   True)}
    assert names == k1 | {"closed.hc_kernel_share",
                          "closed.hc_kernel_roofline",
                          "closed.hc.mixes_per_step"}
    assert len(bench["workloads"]) == 12 and len(bench["configs"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_parameter_counts_are_the_issues_reckoning(x4):
    attn = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
            + 4096 * 3584)
    assert axk1.attention_params(x4) == attn == 28_409_856
    assert axk1.expert_params(x4) == 3 * 3584 * 1024 == 11_010_048
    assert axk1.head_params(x4) == 3584 * 131072
    dense = 3 * 3584 * 9216
    assert dense == 99_090_432
    # the mixing: two branches a layer, phi [4 x 3584, 24], b and 3 gains
    assert entry.mix_params(x4) == 80 * (14336 * 24 + 24 + 3)
    assert 2 * 14336 * 24 == 688_128  # 0.69 M a layer
    # an expert layer HERE: attention, shared expert, sixteen experts
    layer = attn + 17 * 11_010_048
    assert layer == 215_580_672  # 0.216 GB int8
    # weights on the device: int8 projections, bf16 embedding, router and
    # phi, the whole vocabulary, all 40 layers: 9.9 GB
    weights = (38 * layer + 2 * (attn + dense) + 3584 * 131072
               + 2 * 3584 * 131072 + 2 * 38 * 3584 * 64
               + 2 * entry.mix_params(x4))
    assert weights == pytest.approx(9.93e9, rel=5e-3)
    assert axk1.kv_bytes_per_token(x4) == 40 * 576 * 2 == 46_080
    # 640 lanes a row on the device: 51.2 KB a cached token, 4.19 GB
    assert 40 * 640 * 2 == 51_200
    assert x4["serving"]["n_pages"] * 128 * 51_200 == 4_194_304_000


def test_decode_step_counts_the_mixings_least_bytes(x4):
    work = entry.decode_step(x4, batch=128, context=320)
    base = axk1.decode_step(x4, batch=128, context=320)
    # a branch reads a token's streams once and writes them once (57 KB),
    # and its phi once a step
    mix = 80 * (128 * 2 * 4 * 3584 * 2 + 24 * 14336 * 2)
    assert work["bytes"] == pytest.approx(base["bytes"] + mix)
    assert mix == pytest.approx(0.642e9, rel=1e-2)
    assert work["flops"] > base["flops"]
    # every held expert is hit in every step: 16 * (1 - (15/16)^128)
    assert axk1.experts_hit(x4, 128) == pytest.approx(16.0, abs=0.01)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    # weights 8.9 GB, latent rows 1.9 GB, the streams 0.64 GB: 14 ms
    assert work["bytes"] == pytest.approx(11.5e9, rel=3e-2)
    assert least["seconds"] == pytest.approx(0.0140, rel=5e-2)
    pre = entry.prefill(x4, prompt_tokens=1024, mean_prompt=256, programs=1)
    assert pre["bytes"] == pytest.approx(
        axk1.prefill(x4, 1024, 256, 1)["bytes"]
        + 80 * (1024 * 2 * 4 * 3584 * 2 + 24 * 14336 * 2))


def test_hc_kernel_work_is_a_hundred_kilobytes_a_token_and_branch(x4):
    # a step's 160 calls at 128 tokens: hc_pre reads the streams (28.7 KB)
    # and writes u (7.2 KB); hc_post reads the streams and y and writes the
    # streams: 14 x 3584 x 2 B = 100,352 B, plus 1 KB of coefficients
    work = entry.hc_kernel(x4, calls=160, batch=128)
    a_token = 14 * 3584 * 2 + 2 * 128 * 4
    assert a_token == 101_376
    assert work["bytes"] == pytest.approx(
        80 * (128 * a_token + 24 * 14336 * 2))
    assert work["bytes"] == pytest.approx(1.09e9, rel=1e-2)
    assert work["flops"] / work["bytes"] < 10  # bound by its bytes
    one = entry.hc_kernel(x4, calls=2, batch=128)
    assert one["bytes"] * 80 == pytest.approx(work["bytes"])


# -- the reference against the program, logits and not tokens ---------------

def _tiny_model(**over):
    from generativeaiexamples_tpu.models import latent_moe

    c = tiny_file(**over)
    mcfg = entry.model_config(c)
    return c, mcfg, latent_moe, entry.init_params(c, mcfg, 2**31 + 57,
                                                  [None])[0]


def test_the_reference_is_the_programs_forward_at_a_tiny_size():
    c, mcfg, latent_moe, params = _tiny_model()
    assert mcfg.hc_mult == 4 and "hc_attn_phi" in params["layers"]
    ids = np.random.default_rng(5).integers(1, 512, 29).astype(np.int32)
    ref, ref_choice = entry.reference_forward(c, params, ids)
    got, choice = latent_moe.forward(params, mcfg, jnp.asarray(ids)[None],
                                     use_pallas=False)
    top = float(np.abs(ref).max())
    assert np.abs(np.asarray(got[0]) - np.asarray(ref)).max() / top < 2e-3
    assert np.mean(np.sort(np.asarray(choice)[:, 0], -1)
                   == np.sort(np.asarray(ref_choice), -1)) == 1.0


@pytest.mark.parametrize("what,change,least", [
    ("two passes where twenty are asked", dict(hc_sinkhorn_iters=2), 0.01),
    ("one stream", dict(hc_mult=1), 0.05),
])
def test_a_reference_of_another_mixing_disagrees(what, change, least):
    """The comparison can tell: the program with fewer passes, or with no
    mixing at all, gives other logits than the reference of the file
    (after 8 branches here; the served model has 80)."""
    import dataclasses

    c, mcfg, latent_moe, params = _tiny_model()
    ids = np.random.default_rng(6).integers(1, 512, 24).astype(np.int32)
    ref = np.asarray(entry.reference_logits(c, params, ids))
    other, _ = latent_moe.forward(
        params, dataclasses.replace(mcfg, **change), jnp.asarray(ids)[None],
        use_pallas=False)
    assert np.abs(np.asarray(other[0]) - ref).max() / np.abs(ref).max() \
        > least, what


def test_a_program_before_several_streams_fails_cleanly(monkeypatch):
    """On a program without models/hyper_connections.py (the parent of the
    PR that added it) the entry ends the run at once with a message."""
    import builtins
    real = builtins.__import__

    def no_streams(name, *a, **kw):
        if name.endswith("models") and "hyper_connections" in (a[2] or ()):
            raise ImportError("cannot import name 'hyper_connections'")
        return real(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", no_streams)
    with pytest.raises(SystemExit, match="cannot run architecture 'xing4'"):
        entry.model_config(tiny_file())


# -- the rehearsal of the cell at a tiny size --------------------------------

def test_tiny_cell_through_run_cell():
    bench = bench_run.load_benchmark()
    metrics = (bench_run.cell_metrics(bench, CELL, False)
               + bench_run.cell_metrics(bench, CELL, True))
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, tiny_file(), tiny.CLOSED, metrics,
        seed=2**31 + 57, seconds=3.0, trace=False, allow_cpu=True)
    json.dumps(out)
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_asked"] == out["checks"]["tokens_generated"]
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert 0 < out["metrics"]["closed.sched.occupancy"]["value"] <= 4
    # live slots x 2 branches x 4 layers a step
    mixes = out["metrics"]["closed.hc.mixes_per_step"]["value"]
    assert 0 < mixes <= 4 * 2 * 4 and mixes % 8 == 0
    assert out["metrics"]["closed.moe.load_max_over_mean"]["value"] >= 1.0
    # no trace, no kernel on the CPU: the trace's readers say nothing
    assert "closed.hc_kernel_share" not in out["metrics"]
    assert "closed.hc_kernel_roofline" not in out["metrics"]
