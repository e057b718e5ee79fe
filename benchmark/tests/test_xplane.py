"""The trace reduction: arithmetic on hand-made events, then the whole
reduction against a small trace recorded on the chip."""

import os

import pytest

from benchmark.harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_of_overlapping_intervals():
    evs = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 0.5), ("d", 3.1, 0.1)]
    assert xplane.union_seconds(evs) == pytest.approx(2.0)


def test_self_time_takes_children_out_of_their_parent():
    evs = [("while.1", 0.0, 10.0), ("fusion.2", 1.0, 3.0),
           ("fusion.3", 5.0, 2.0), ("copy.4", 12.0, 1.0)]
    got = {n: s for n, _, s in xplane.self_times(evs)}
    assert got == pytest.approx({"while.1": 5.0, "fusion.2": 3.0,
                                 "fusion.3": 2.0, "copy.4": 1.0})


@pytest.mark.parametrize("raw,want", [
    ("jit_decode_multi_step(123456)", "decode_multi_step"),
    ("jit_prefill_batch_step", "prefill_batch_step"),
])
def test_program_names(raw, want):
    assert xplane.program_of(raw) == want


@pytest.mark.parametrize("raw,want", [
    ("fusion.123", "fusion"), ("%fusion.12 = bf16[8] fusion(...)", "fusion"),
    ("paged_attention_int8.3", "paged_attention_int8"),
    ("all-reduce.7", "all-reduce"), ("copy", "copy"),
])
def test_operation_kinds(raw, want):
    assert xplane.op_kind(raw) == want


def test_reduce_on_hand_made_planes():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_decode_multi_step(1)", 0.0, 1.0),
                            ("jit_decode_multi_step(1)", 1.5, 1.0),
                            ("jit_prefill_batch_step(2)", 3.0, 2.0)],
            "XLA Ops": [("fusion.1", 0.0, 0.6), ("paged_attention.2", 0.6, 0.4),
                        ("fusion.1", 1.5, 0.6), ("paged_attention.2", 2.1, 0.4),
                        ("fusion.9", 3.0, 2.0)],
        },
        "/host:CPU": {"sched": [("engine._loop", 0.9, 0.7)]},
    }
    got = xplane.reduce(planes)
    assert got["window_s"] == pytest.approx(5.0)
    assert got["busy_s"] == pytest.approx(4.0)
    dec = got["programs"]["decode_multi_step"]
    assert dec["executions"] == 2 and dec["device_s"] == pytest.approx(2.0)
    assert dec["kernel_calls"] == {"fusion": 2, "paged_attention": 2}
    assert got["ops"]["prefill_batch_step/fusion"] == pytest.approx(2.0)
    assert got["device_ops"][0] == ["prefill_batch_step/fusion",
                                    pytest.approx(2.0)]
    gaps = dict(got["idle_gaps"])
    assert gaps == pytest.approx({"engine._loop": 0.5,
                                  "host_idle_or_untraced": 0.5})


def test_no_device_plane_reads_nothing():
    assert xplane.reduce({"/host:CPU": {"t": [("x", 0.0, 1.0)]}}) is None


def test_reduce_on_the_recorded_trace():
    """data/tiny.xplane.pb was recorded on a TPU v5 lite (PR 24): three
    executions of a jitted `decode_multi_step` (a fori_loop of 4 matmul
    + tanh steps), a 20 ms sleep, two of `prefill_batch_step`."""
    got = xplane.reduce(xplane.load(os.path.join(DATA, "tiny.xplane.pb")))
    assert got["chips"] == 1
    dec = got["programs"]["decode_multi_step"]
    pre = got["programs"]["prefill_batch_step"]
    assert dec["executions"] == 3 and pre["executions"] == 2
    assert dec["kernel_calls"]["convolution_tanh_fusion"] == 12  # 3 x 4
    assert dec["kernel_calls"]["while"] == 3
    assert dec["device_s"] == pytest.approx(91.055e-6, rel=1e-3)
    assert pre["device_s"] == pytest.approx(35.503e-6, rel=1e-3)
    # the loop bodies' time is taken out of the enclosing `while`
    assert got["ops"]["decode_multi_step/while"] < 1e-6
    assert got["ops"]["decode_multi_step/convolution_tanh_fusion"] \
        == pytest.approx(69.41e-6, rel=1e-3)
    assert got["busy_s"] == pytest.approx(126.492e-6, rel=1e-3)
    assert got["window_s"] == pytest.approx(24.5525e-3, rel=1e-3)
    assert got["busy_s"] <= dec["device_s"] + pre["device_s"] + 1e-9
    assert got["device_ops"][0][0] == "decode_multi_step/convolution_tanh_fusion"
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-3)
