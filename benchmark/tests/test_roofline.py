"""The llama entry's counts of a step's work, and the least time for
them, against counts worked by hand for Mistral-7B."""

import json
import os

import pytest

from benchmark.architectures import llama as entry
from benchmark.harness import roofline

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def m7():
    with open(os.path.join(BENCH_DIR, "configs",
                           "mistral-7b-v0.3-int8.json")) as fh:
        return json.load(fh)


def test_parameter_counts(m7):
    # q 4096x4096, k and v 4096x1024, o 4096x4096, gate/up/down 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert entry.layer_matmul_params(m7) == layer
    assert entry.matmul_params(m7) == 32 * layer + 4096 * 32768
    assert entry.matmul_params(m7) == 7_113_539_584


def test_kv_bytes_per_token(m7):
    # 32 layers x (K and V: 2 x 8 heads x 128 int8 + 2 x 8 float32 scales)
    assert entry.kv_bytes_per_token(m7) == 32 * (2048 + 64) == 67_584


def test_decode_step_is_memory_bound_at_64_slots(m7):
    work = entry.decode_step(m7, batch=64, context=300)
    assert work["bytes"] == pytest.approx(
        7_113_539_584 + 64 * 300 * 67_584 + 64 * 67_584)
    assert work["flops"] == pytest.approx(
        2 * 64 * 7_113_539_584 + 4 * 64 * 300 * 32 * 128 * 32)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "memory"
    # 8.415e9 bytes at 819 GB/s
    assert least["seconds"] == pytest.approx(8.4154e9 / 819e9, rel=1e-3)


def test_prefill_is_compute_bound(m7):
    work = entry.prefill(m7, prompt_tokens=2048, mean_prompt=2048,
                         programs=1)
    body = 32 * 218_103_808
    assert work["flops"] == pytest.approx(
        2 * 2048 * body + 2 * 2048 * 2048 * 32 * 128 * 32
        + 2 * 4096 * 32768)
    peaks = roofline.load_peaks(BENCH_DIR, "TPU v5 lite")
    least = roofline.least_seconds(work, peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(work["flops"] / 197e12)


def test_tensor_parallel_divides_the_work(m7):
    one = entry.decode_step(m7, 64, 300, chips=1)
    four = entry.decode_step(m7, 64, 300, chips=4)
    assert four["bytes"] == pytest.approx(one["bytes"] / 4)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.load_peaks(BENCH_DIR, "TPU v99")
    assert roofline.load_peaks(BENCH_DIR, "TPU v5 lite")["hbm_gbps"] == 819.0
