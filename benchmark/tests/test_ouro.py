"""The ouro entry: its counts of a step's work against counts worked by
hand for Ouro-2.6B, its reader, and a tiny looped configuration through
`run_cell` on the CPU (a rehearsal, never a measurement)."""

import copy
import json
import os

import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.architectures import ouro as entry
from benchmark.harness import roofline, xplane
from benchmark.readers import trace_kernel
from benchmark.tests import test_rehearsal as tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
CELL = "ouro2.6b.decode-closed32"


@pytest.fixture(scope="module")
def o26():
    with open(os.path.join(BENCH_DIR, "configs", "ouro-2.6b-int8.json")) as fh:
        return json.load(fh)


def test_the_file_resolves_to_this_entry_at_the_published_sizes(o26):
    assert architectures.load(o26) is entry
    assert o26["reduced"] == [] and o26["total_ut_steps"] == 4
    mcfg = entry.model_config(o26)
    assert (mcfg.dim, mcfg.n_layers, mcfg.n_heads, mcfg.n_kv_heads,
            mcfg.head_dim, mcfg.mlp_dim, mcfg.vocab_size) == (
        2048, 48, 16, 16, 128, 5632, 49152)
    assert (mcfg.n_passes, mcfg.post_norms, mcfg.cache_rows) == (4, True, 192)
    assert entry.step_kernel_calls(o26) == 192


def test_parameter_counts(o26):
    # q, k, v, o 2048x2048 (16 heads of 128, no grouping); gate/up/down
    # 2048x5632
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert block == 51_380_224
    assert entry.block_params(o26) == 48 * block == 2_466_250_752
    assert entry.head_params(o26) == 2048 * 49152 == 100_663_296


def test_kv_bytes_per_token(o26):
    # 192 rows x (K and V: 2 x 16 heads x 128 int8 + 2 x 16 float32 scales)
    assert entry.kv_bytes_per_token(o26) == 192 * (4096 + 128) == 811_008
    # a page of 128 tokens, and the configuration's 112 of them
    assert 128 * 811_008 == 103_809_024
    assert o26["serving"]["n_pages"] * 103_809_024 == 11_626_610_688


def test_decode_step_reads_the_blocks_once_a_pass_and_the_head_once(o26):
    work = entry.decode_step(o26, batch=32, context=208)
    weights = 4 * 2_466_250_752 + 100_663_296  # int8: a byte each
    assert work["bytes"] == pytest.approx(
        weights + 32 * 208 * 811_008 + 32 * 811_008)
    assert work["flops"] == pytest.approx(
        2 * 32 * weights + 4 * 32 * 208 * 16 * 128 * 192)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    # 15.39e9 bytes at 819 GB/s: ISSUE 29's 18.8 ms
    assert least["seconds"] == pytest.approx(15.3897e9 / 819e9, rel=1e-3)
    assert least["seconds"] == pytest.approx(0.0188, rel=5e-3)


def test_prefill_counts_every_pass(o26):
    work = entry.prefill(o26, prompt_tokens=512, mean_prompt=128, programs=1)
    assert work["flops"] == pytest.approx(
        2 * 512 * 4 * 2_466_250_752 + 2 * 512 * 128 * 16 * 128 * 192
        + 2 * 4 * 100_663_296)
    assert work["bytes"] == pytest.approx(
        4 * 2_466_250_752 + 100_663_296 + 512 * 811_008)


def test_attention_kernel_work_is_one_row_a_call(o26):
    # 192 calls (one step) of 32 sequences of 208 tokens: K and V codes
    # and scales of ONE row each, q in and the output back in bf16
    work = entry.attention_kernel(o26, calls=192, batch=32, context=208)
    row = 4096 + 128
    assert work["bytes"] == pytest.approx(
        192 * 32 * (208 * row + 2 * 16 * 128 * 2))
    assert work["flops"] == pytest.approx(192 * 4 * 32 * 208 * 16 * 128)
    # the cache read of a whole step, with the queries: a third of the
    # step's bytes
    step = entry.decode_step(o26, 32, 208)
    assert 0.33 < work["bytes"] / step["bytes"] < 0.37


TINY_OURO = dict(
    copy.deepcopy(tiny.TINY), architecture="ouro", model_type="ouro",
    num_hidden_layers=3, num_key_value_heads=4, total_ut_steps=2,
    early_exit_threshold=1)


def test_trace_kernel_reader_on_a_recorded_trace():
    """data/tiny.xplane.pb: three executions of `decode_multi_step`, four
    `convolution_tanh_fusion` calls each (test_xplane.py); here that op
    plays the kernel."""
    trace = xplane.reduce(xplane.load(os.path.join(DATA, "tiny.xplane.pb")))
    counters = {"decode_steps": 0, "busy_slots_acc": 0}
    ctx = {"trace": trace, "config": TINY_OURO, "chips": 1,
           "traffic": tiny.CLOSED,
           "peaks": roofline.load_peaks(BENCH_DIR, "TPU v5 lite"),
           "engine": {"trace_open": counters,
                      "trace_close": {"decode_steps": 2,
                                      "busy_slots_acc": 8}}}
    args = ("decode_multi_step", "convolution_tanh_fusion")
    share = trace_kernel.read(ctx, *args, "share")
    device_s = sum(s for k, s in trace["ops"].items()
                   if k == "decode_multi_step/convolution_tanh_fusion")
    assert share == pytest.approx(100.0 * device_s / trace["busy_s"])
    assert 0 < share < 100
    # 12 calls, 4 slots busy, prompts 8-24 and answers 4-8: context 19
    work = entry.attention_kernel(TINY_OURO, 12, 4.0, 16 + 6 / 2)
    want = 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
    assert trace_kernel.read(ctx, *args, "roofline") == pytest.approx(want)
    # an entry without the function, a trace without the kernel, no trace
    assert trace_kernel.read(dict(ctx, config=tiny.TINY), *args,
                             "roofline") is None
    assert trace_kernel.read(ctx, "decode_multi_step", "no_such_kernel",
                             "share") is None
    assert trace_kernel.read(dict(ctx, trace=None), *args, "share") is None


def test_tiny_looped_cell_through_run_cell():
    bench = bench_run.load_benchmark()
    metrics = (bench_run.cell_metrics(bench, CELL, False)
               + bench_run.cell_metrics(bench, CELL, True))
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, TINY_OURO, tiny.CLOSED, metrics,
        seed=2**31 + 29, seconds=3.0, trace=False, allow_cpu=True)
    json.dumps(out)
    assert out["failed"] == 0, out
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_asked"] == out["checks"]["tokens_generated"]
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert 0 < out["metrics"]["closed.sched.occupancy"]["value"] <= 4


def test_a_reference_of_one_pass_fewer_reads_not_correct(monkeypatch):
    """The served model runs 2 passes; a reference told 1 disagrees."""
    real = entry.reference_logits
    monkeypatch.setattr(
        entry, "reference_logits",
        lambda config, params, ids: real(dict(config, total_ut_steps=1),
                                         params, ids))
    bench = bench_run.load_benchmark()
    out = bench_run.run_cell(
        {"name": CELL, "chips": 1}, TINY_OURO, tiny.CLOSED,
        bench_run.cell_metrics(bench, CELL, False), seed=2**31 + 31,
        seconds=2.0, trace=False, allow_cpu=True)
    assert out["failed"] == 0
    assert not out["checks"]["reference"]["ok"] and not out["correct"]
