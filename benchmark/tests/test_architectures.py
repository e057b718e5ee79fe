"""The seam is enough: an architecture that lives only in test files
(data/arch_twice.py, named by a tiny configuration) goes through
`run.run_cell` and the readers with no edit to run.py, harness/ or
readers/. A rehearsal on the CPU, never a measurement."""

import copy
import os
import sys

import pytest

from benchmark import architectures
from benchmark import run as bench_run
from benchmark.harness import xplane
from benchmark.readers import trace_program
from benchmark.tests import test_rehearsal as tiny
from benchmark.tests.data import arch_twice

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "mistral7b.decode-closed64"


@pytest.fixture
def twice(monkeypatch):
    """A tiny configuration naming the test-only architecture."""
    monkeypatch.setitem(sys.modules, "benchmark.architectures.twice",
                        arch_twice)
    return dict(copy.deepcopy(tiny.TINY), architecture="twice")


def _run(config):
    bench = bench_run.load_benchmark()
    return bench_run.run_cell(
        {"name": CELL, "chips": 1}, config, tiny.CLOSED,
        bench_run.cell_metrics(bench, CELL, False), seed=2**31 + 29,
        seconds=2.0, trace=False, allow_cpu=True)


def test_a_reference_that_is_not_the_served_model_reads_not_correct(twice):
    out = _run(dict(twice, reverse_reference_layers=True))
    ref = out["checks"]["reference"]
    assert out["failed"] == 0 and len(ref["served"]) == 3
    assert not ref["ok"] and not out["correct"], out["checks"]
    assert ref["worst_shortfall"] > 0.05  # the configuration's rel_tol
    lines = bench_run.check_lines(out)  # what the run leaves on stderr
    assert lines[0] == "check reference.worst_shortfall %s limit 0.05" % (
        ref["worst_shortfall"])
    assert lines[-1] == "correct False"


def test_the_right_reference_through_the_same_entry_reads_correct(twice):
    out = _run(twice)
    assert out["correct"], out["checks"]
    assert out["checks"]["reference"]["worst_shortfall"] <= 0.05
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0


def test_step_kernel_calls_scale_the_steps_read_from_a_trace(twice):
    """`load` finds the entry by the configuration's name.
    data/tiny.xplane.pb: three executions of `decode_multi_step`, four
    `convolution_tanh_fusion` calls each (test_xplane.py)."""
    assert architectures.load(twice) is arch_twice
    trace = xplane.reduce(xplane.load(os.path.join(DATA, "tiny.xplane.pb")))

    def steps(config):
        ctx = {"trace": trace, "config": config}
        prog = trace_program.program(ctx, "decode_multi_step")
        return trace_program.decode_steps(ctx, prog, "convolution_tanh_fusion")

    assert steps(tiny.TINY) == 12 / 2       # llama: once per layer, 2 layers
    assert steps(twice) == 12 / 4           # the test-only entry: twice
    ctx = {"trace": trace, "config": twice}
    assert trace_program.read(ctx, "decode_multi_step", "step",
                              "convolution_tanh_fusion") == pytest.approx(
        2 * trace_program.read(dict(ctx, config=tiny.TINY),
                               "decode_multi_step", "step",
                               "convolution_tanh_fusion"))


@pytest.mark.parametrize("name", ["no_such_decoder", "../llama", ""])
def test_unknown_architecture_names_the_known_ones(name):
    with pytest.raises(ValueError) as e:
        architectures.load({"architecture": name})
    assert "llama" in str(e.value) and repr(name) in str(e.value)
    with pytest.raises(ValueError) as e:
        architectures.load_encoder({"architecture": name})
    assert "bert" in str(e.value)


def test_an_entry_that_lacks_an_item_is_refused(monkeypatch):
    import types

    half = types.ModuleType("benchmark.architectures.half")
    half.model_config = lambda config: None
    monkeypatch.setitem(sys.modules, half.__name__, half)
    with pytest.raises(TypeError, match="step_kernel_calls"):
        architectures.load({"architecture": "half"})


def test_absent_means_llama():
    assert architectures.load({}).__name__ == "benchmark.architectures.llama"
    assert "llama" in architectures.known()
