"""``trace_reduce`` on a small trace recorded on a TPU v5e (PR 23): three
executions of ``greedy_balanced_assign`` at 4,096 x 1,024, 20 ms apart."""

from pathlib import Path

import pytest

from benchmark import trace_reduce

TRACE = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


def test_program_name():
    assert trace_reduce.program_name("jit__class_refresh_device(123)") == "_class_refresh_device"
    assert trace_reduce.program_name("jit_greedy_balanced_assign(9)") == "greedy_balanced_assign"


def test_recorded_trace_reduces_to_known_numbers():
    out = trace_reduce.reduce(str(TRACE))
    prog = out["programs"]["greedy_balanced_assign"]
    assert prog["calls"] == 3
    # Device durations in the recording: 309,527 + 309,496 + 309,517 ns.
    assert prog["seconds"] == pytest.approx(928.54e-6, rel=1e-3)
    assert out["busy_s"] == pytest.approx(prog["seconds"], rel=1e-6)
    assert out["devices"] == 1
    assert 0.04 < out["window_s"] < 0.05  # first start to last end
    assert out["busy_s"] < out["window_s"]
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    # Two gaps between three executions, under the host's bench.wave spans.
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6
    )


def test_harness_spans_ride_the_window_marks():
    # Without marks in the trace the caller's spans are left out.
    out = trace_reduce.reduce(str(TRACE), [("bench.x", 0, 10)], {"bench.window.start": 0, "bench.window.end": 1})
    assert out["harness_spans"] == [] and out["clock_offset_ns"] is None
