"""The reduction from a profiler trace to busy time, kernel time and
labelled idle gaps: on synthetic intervals, and on a short trace recorded on
a TPU v5e (``data/batch-unique.xplane.pb``: bench/tools/record_trace.py,
qwen3-0.6b.batch-unique, a 0.4 s window; kept are the device's op and
module lines, the harness's own host spans, and the events within 20 ms of
the window)."""

import pathlib

import pytest

from perfkit import tracered
from perfkit.tracered import Interval

DATA = pathlib.Path(__file__).parent / "data" / "batch-unique.xplane.pb"


def test_union_and_gaps():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (10.0, 11.0)]
    assert tracered.union_length(ivs, 0.0, 5.0) == 3.0
    assert tracered.idle_gaps(ivs, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert tracered.idle_gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_reduce_labels_gaps_by_innermost_host_span():
    host = [Interval(tracered.WINDOW, 0.0, 10.0),
            Interval("engine.step", 0.0, 6.0),
            Interval("bench.idle", 6.0, 10.0)]
    dev = {"/device:TPU:0": [Interval("jit_step:%while.1", 1.0, 4.0),
                             Interval("jit_step:%fused_sgmv.3", 1.5, 2.0),
                             Interval("jit_step:%fused_sgmv.4", 2.0, 2.5),
                             Interval("jit_step:%ragged_extend.1", 3.0, 3.5),
                             Interval("jit_where:%select", 5.0, 5.5)]}
    s = tracered.reduce(host, dev)
    assert s.window_s == 10.0 and s.busy_s == pytest.approx(3.5)
    # the while loop contains the kernels: only they are counted per op
    assert "jit_step:%while.1" not in s.op_s
    assert s.kernel_s("fused_sgmv") == pytest.approx(1.0)
    assert s.kernel_s("ragged_extend") == pytest.approx(0.5)
    assert s.kernel_s("paged_attention") == 0.0
    assert s.gaps_by_label() == [("bench.idle", pytest.approx(4.5)),
                                 ("engine.step", pytest.approx(2.0))]


def test_recorded_chip_trace():
    host, devs = tracered.load(str(DATA))
    assert list(devs) == ["/device:TPU:0"]
    s = tracered.reduce(host, devs)
    assert s.window_s == pytest.approx(0.40374923)
    # the batch cell keeps the device busy: 91% of this window
    assert s.busy_s / s.window_s == pytest.approx(0.9105, abs=1e-3)
    # the batch cell's window runs the mixed step and decode, each with
    # the fused LoRA projection kernel in every layer
    assert s.kernel_s("fused_sgmv") > 0
    assert any(n.startswith("jit_step:") for n in s.op_s)
    labels = dict(s.gaps_by_label())
    assert "engine.step" in labels
    idle = s.window_s - s.busy_s
    assert sum(labels.values()) == pytest.approx(idle, rel=1e-6)
