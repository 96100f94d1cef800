"""The trace reduction on a small trace recorded on a TPU v5 lite by
``tools/record_trace.py``: two jitted programs, three ticks inside
``bench.window``, host sleeps inside ``serve.submit`` between them."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

pytest.importorskip("jax")
import trace_reduce  # noqa: E402

TRACE = BENCH / "tests" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(str(TRACE))


def test_window_and_busy(red):
    assert red.n_devices == 1
    assert 0.005 < red.window_s < 0.1          # three ticks, 2 ms sleeps
    assert 0 < red.busy_s < red.window_s
    assert 0.5 < red.idle_share < 1.0           # mostly host sleeps


def test_programs_by_jit_name(red):
    assert set(red.programs) == {"prefill_step", "slot_decode"}
    assert 2 <= len(red.programs["slot_decode"]) <= 3
    assert all(t > 0 for ts in red.programs.values() for t in ts)
    assert trace_reduce.program_seconds(red, ["slot_decode"]) == \
        red.programs["slot_decode"]
    assert trace_reduce.program_seconds(None, ["slot_decode"]) == []


def test_ops_and_gaps(red):
    assert red.ops and red.ops[0][1] >= red.ops[-1][1]
    labels = [label for label, _ in red.gaps]
    assert labels[0] == "serve.submit"          # the longest gaps: sleeps
    assert all(s > 0 for _, s in red.gaps)
    assert sorted((s for _, s in red.gaps), reverse=True) == \
        [s for _, s in red.gaps]
    b = trace_reduce.breakdown(red)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_merge_and_program_name():
    assert trace_reduce._merge([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace_reduce.program_name("jit_slot_decode(123)") == \
        "slot_decode"
    assert trace_reduce.op_name(
        "%copy.9 = bf16[4,8]{1,0:T(8,128)} copy(bf16[4,8]{1,0} %x)") == \
        "%copy.9 = bf16[4,8]"
