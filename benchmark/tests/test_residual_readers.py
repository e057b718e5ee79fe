"""The two readers PR 57 added, on recordings. data/residual_mix_ctx.json:
the engine's `residual_mix` (kind 26) and some `moe_load` (kind 19)
flight events of one CPU rehearsal of the tiny four-stream cell
(benchmark/tests/test_xing4.py::tiny_file: 4 layers, 4 slots, blocks of 2
steps; 332 blocks landed, 281 of them inside the 3 s window, with one to
four live slots: 8 mixes a live slot and step). data/program_ctx.json and
data/host_pause_ctx.json are recordings of programs with ONE stream:
the reader gives None. data/tiny.xplane.pb is the recorded trace the
other kernel readers' tests use; its `convolution_tanh_fusion` plays the
kernel."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.architectures import xing4
from benchmark.harness import roofline, stats, xplane
from benchmark.readers import engine_residual_mix, trace_entry_kernel
from benchmark.tests import test_rehearsal as tiny
from benchmark.tests.test_xing4 import CELL, tiny_file

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")


def _load(name):
    with open(os.path.join(DATA, name)) as fh:
        return json.load(fh)


def test_the_mixes_a_step_are_the_windows_median():
    ctx = _load("residual_mix_ctx.json")
    inside = [e["a"] for e in ctx["engine"]["events"]
              if e["kind"] == 26 and stats.in_window(e["t"], 3.0)]
    assert len(inside) == 281 and set(inside) == {8.0, 16.0, 24.0, 32.0}
    assert engine_residual_mix.read(ctx, "a") == 16.0
    # the stream's bytes a token: 4 streams x 64 wide x float32
    assert engine_residual_mix.read(ctx, "b") == 1024.0
    # a window that holds the ramp's first blocks alone: one live slot
    early = dict(ctx, engine={"events": [
        dict(e, t=e["t"] + 5.9) for e in ctx["engine"]["events"]]},
        seconds=0.1)
    assert engine_residual_mix.read(early, "a") == 8.0


@pytest.mark.parametrize("recording", ["program_ctx.json",
                                       "host_pause_ctx.json",
                                       "timeline_ctx.json"])
def test_a_program_with_one_stream_says_nothing(recording):
    ctx = _load(recording)
    assert engine_residual_mix.read(ctx, "a") is None
    assert engine_residual_mix.read(ctx, "b") is None


def test_the_metric_files_name_the_readers_and_the_cell_alone_lists_them():
    bench = bench_run.load_benchmark()
    new = {"closed.hc_kernel_share": ("trace_kernel", "lower"),
           "closed.hc_kernel_roofline": ("trace_entry_kernel", "higher"),
           "closed.hc.mixes_per_step": ("engine_residual_mix", "higher")}
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["better"] == new[m["name"]][1]
            assert m["moves"] == "out_tokens_per_s"
            with open(os.path.join(BENCH_DIR, "metrics",
                                   m["name"] + ".json")) as fh:
                spec = json.load(fh)
            assert spec["reader"] == new[m["name"]][0]
            assert {k: spec[k] for k in ("layer", "unit", "better", "source",
                                         "moves")} \
                == {k: m[k] for k in ("layer", "unit", "better", "source",
                                      "moves")}
    assert sum(m["name"] in new for m in bench["per_layer"]) == 3
    roof = json.load(open(os.path.join(
        BENCH_DIR, "metrics", "closed.hc_kernel_roofline.json")))
    assert roof["params"] == {"program_name": "decode_multi_step",
                              "kernel": "hc_", "work": "hc_kernel"}


def test_the_entry_kernel_reader_takes_the_work_function_by_name():
    """Three executions of `decode_multi_step`, four
    `convolution_tanh_fusion` calls each: 12 calls, here the two mixing
    kernels' (6 branches)."""
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
    work = xing4.hc_kernel(config, 12, 4.0)
    want = 100.0 * roofline.least_seconds(work, ctx["peaks"])["seconds"] \
        / device_s
    assert trace_entry_kernel.read(ctx, *args, work="hc_kernel") \
        == pytest.approx(want)
    # the same reader with another of the entry's functions: A.X-K1's
    moe = xing4.moe_kernel(config, 12, 4.0)
    assert trace_entry_kernel.read(ctx, *args, work="moe_kernel") \
        == pytest.approx(100.0 * roofline.least_seconds(
            moe, ctx["peaks"])["seconds"] / device_s)
    # a program without the kernel (every one before several streams), an
    # entry without the function, no trace, no step: nothing, and no raise
    assert trace_entry_kernel.read(ctx, "decode_multi_step", "hc_",
                                   work="hc_kernel") is None
    assert trace_entry_kernel.read(dict(ctx, config=tiny.TINY), *args,
                                   work="hc_kernel") is None
    assert trace_entry_kernel.read(dict(ctx, trace=None), *args,
                                   work="hc_kernel") is None
    still = dict(ctx, engine={"trace_open": {"decode_steps": 3,
                                             "busy_slots_acc": 0},
                              "trace_close": {"decode_steps": 3,
                                              "busy_slots_acc": 0}})
    assert trace_entry_kernel.read(still, *args, work="hc_kernel") is None
