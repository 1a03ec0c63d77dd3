"""The reduction from a profiler trace to device metrics, and the roofline."""
import pathlib

import pytest

from bench.lib import roofline
from bench.lib.trace import Trace, gaps, merge

DATA = pathlib.Path(__file__).with_name("data")
#: three steps of the ridge hypergradient at 64 x 512 on one TPU v5 lite,
#: each dispatched, waited for and followed by a 2.5 ms host pause
RECORDED = DATA / "spd_step_v5e.trace.json.gz"


def _meta(pid, tid, proc, thread):
    return [{"ph": "M", "name": "process_name", "pid": pid,
             "args": {"name": proc}},
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
             "args": {"name": thread}}]


def _op(ts, dur, name, scope=""):
    return {"ph": "X", "pid": 1, "tid": 3, "ts": ts, "dur": dur,
            "name": name, "args": {"tf_op": scope}}


def _span(ts, dur, name):
    return {"ph": "X", "pid": 9, "tid": 7, "ts": ts, "dur": dur, "name": name}


def synthetic():
    """A 100 us window: ops at 10-30 (two overlapping), 50-60 and 95-110;
    host spans: dispatch 0-38, wait 38-70, idle 70-100."""
    return Trace(_meta(1, 3, "/device:TPU:0", "XLA Ops")
                 + _meta(9, 7, "/host:CPU", "python3")
                 + [_op(10, 15, "fusion.1", "jit(step)/jvp(spd_solve)/add:"),
                    _op(20, 10, "k.1", "jit(step)/transpose(jvp(spd_solve))/pallas_call:"),
                    _op(50, 10, "fusion.2", "jit(step)/spd_solver_x/mul:"),
                    _op(95, 15, "copy.3", "G:"),
                    _span(0, 100, "bench.window"), _span(0, 38, "bench.dispatch"),
                    _span(38, 32, "bench.wait"), _span(70, 30, "bench.idle")])


def test_merge_and_gaps():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert gaps([(0, 3), (5, 9)], 0, 10) == [(3, 5), (9, 10)]
    assert gaps([], 2, 4) == [(2, 4)]


def test_busy_idle_and_window():
    t = synthetic()
    assert t.window_s() == pytest.approx(100e-6)
    # union: 10-30, 50-60, 95-100 (clipped to the window)
    assert t.busy_s() == pytest.approx(35e-6)


def test_scope_and_ops():
    t = synthetic()
    # whole-scope matches only: spd_solver_x is another scope
    assert t.scope_s("spd_solve") == pytest.approx(25e-6)
    assert t.scope_s("G") == pytest.approx(5e-6)
    top = t.top_ops(2)
    assert top[0] == ["fusion.1 jit(step)/jvp(spd_solve)/add:", pytest.approx(15e-6)]
    assert len(top) == 2


def test_loop_op_is_not_counted_with_its_body():
    # a while op (0-40) spans its body's two ops; a lone op follows
    t = Trace(_meta(1, 3, "/device:TPU:0", "XLA Ops")
              + [_op(0, 40, "while.5", "jit(call)/while"),
                 _op(0, 10, "k.1", "jit(call)/while/body/jvp(spd_solve)/pallas_call:"),
                 _op(20, 20, "k.2", "jit(call)/while/body/transpose(jvp(spd_solve))/pallas_call:"),
                 _op(50, 5, "copy.1", "")])
    assert [name for name, _ in t.top_ops()] == [
        "k.2 jit(call)/while/body/transpose(jvp(spd_solve))/pallas_call:",
        "k.1 jit(call)/while/body/jvp(spd_solve)/pallas_call:", "copy.1"]
    assert t.scope_s("spd_solve") == pytest.approx(30e-6)
    assert t.busy_s() == pytest.approx(45e-6)


def test_idle_gaps_named_by_host_span():
    # gaps 0-10 (dispatch), 30-50 (mostly wait), 60-95 (mostly idle)
    got = dict(synthetic().idle_gaps())
    assert got == {"bench.dispatch": pytest.approx(10e-6),
                   "bench.wait": pytest.approx(20e-6),
                   "bench.idle": pytest.approx(35e-6)}


def test_recorded_trace():
    t = Trace.from_file(RECORDED)
    assert list(t.ops) == ["/device:TPU:0"]
    # no bench.window span in this recording: the window spans the ops
    assert t.window_s() == pytest.approx(0.01167935625)
    assert t.busy_s() == pytest.approx(0.003578339142)
    assert 0 < t.busy_s() < t.window_s()
    # both kernel calls, forward and transposed, carry the named scope
    names = [name for name, _ in t.top_ops(10)]
    assert any("jvp(spd_solve)/pallas_call" in n and "transpose" not in n
               for n in names)
    assert any("transpose(jvp(spd_solve))/pallas_call" in n for n in names)
    assert t.scope_s("spd_solve") == pytest.approx(0.002668066016)
    idle = dict(t.idle_gaps())
    assert max(idle, key=idle.get) == "bench.host_pause"
    assert sum(idle.values()) == pytest.approx(t.window_s() - t.busy_s())


def test_roofline_floor():
    nbytes = roofline.spd_solve_bytes(64, 512)
    assert nbytes == 4 * 64 * (512 * 512 + 4 * 512) == 67_633_152
    peak = roofline.peaks("TPU v5 lite")
    seconds, bound = roofline.floor_seconds(
        roofline.spd_solve_flops(64, 512), nbytes, peak)
    assert bound == "hbm"
    assert seconds == pytest.approx(nbytes / 819e9)       # 82.6 us


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.peaks("TPU v5e")["bf16_flop_per_s"] == 197e12
