"""The reduction from a profiler trace to busy time, program time,
collective time and labelled idle gaps: on hand-made events whose answers
are worked out below, and on a small trace recorded on a TPU v5e."""
import pathlib

import pytest

from benchmarks.chip import trace_reduce as tr
from benchmarks.chip.harness import Spans

DATA = pathlib.Path(__file__).resolve().parent / "data"
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


def hand_made():
    """A 100 ns window on two devices.  Device 0 runs bench_decode over
    [10, 40) (ops [10, 20) and [15, 30), an all-gather [30, 40)) and
    bench_prefill over [60, 80); device 1 one op over [0, 50) of
    bench_decode.  The host walks over [40, 60) and reads tokens over
    [80, 100)."""
    return [
        ev(HOST, "python", "window", 0, 100),
        ev(HOST, "python", "walk", 40, 20),
        ev(HOST, "python", "token_read", 80, 20),
        ev(HOST, "python", "not_ours", 0, 100),
        ev(D0, "XLA Modules", "jit_bench_decode(17)", 10, 30),
        ev(D0, "XLA Ops", "fusion.1", 10, 10),
        ev(D0, "XLA Ops", "fusion.2", 15, 15),
        ev(D0, "XLA Ops", "all-gather.3", 30, 10),
        ev(D0, "XLA Modules", "jit_bench_prefill(4)", 60, 20),
        ev(D0, "XLA Ops", "fusion.9", 60, 20),
        ev(D0, "XLA Ops", "%while.2 = (s32[]) while(...)", 60, 20),
        ev(D1, "XLA Modules", "jit_bench_decode(17)", 0, 50),
        ev(D1, "XLA Ops", "fusion.1", 0, 50),
        # outside the window: ignored
        ev(D0, "XLA Ops", "fusion.1", 200, 50),
    ]


def test_hand_made():
    r = tr.reduce(hand_made(), Spans.NAMES)
    assert r.window_s == pytest.approx(100e-9)
    assert r.n_devices == 2
    # device 0 busy [10, 40) + [60, 80) = 50; device 1 [0, 50) = 50
    assert r.busy_s == pytest.approx(50e-9)
    dec = r.program("bench_decode")
    assert dec["calls"] == 1 and dec["s"] == pytest.approx(40e-9)
    assert dec["collective_s"] == pytest.approx(5e-9)   # 10 ns on one of 2
    pre = r.program("bench_prefill")
    assert pre["calls"] == 0.5 and pre["s"] == pytest.approx(10e-9)
    assert r.program("absent")["calls"] == 0
    idle = dict(r.idle_by_span)
    # device 0: [0,10) none, [40,60) walk, [80,100) token_read;
    # device 1: [50,100): midpoint 75 lies in no span -> none
    assert idle["walk"] == pytest.approx(10e-9)
    assert idle["token_read"] == pytest.approx(10e-9)
    assert idle["none"] == pytest.approx(30e-9)
    ops = dict(r.ops)
    assert ops["bench_decode/fusion.1"] == pytest.approx(30e-9)
    assert "bench_prefill/fusion.9" in ops
    assert not any("while" in k for k in ops)     # a loop holds its body
    b = r.breakdown(top=2)
    assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) == 2


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([ev(D0, "XLA Ops", "fusion.1", 0, 1)], Spans.NAMES)


def test_union():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_program_names():
    assert tr.program_name("jit_bench_decode(123)") == "bench_decode"
    assert tr.program_name("bench_prefill") == "bench_prefill"


def test_recorded_v5e_trace():
    """``probe.xplane.pb``: three calls of a jitted ``bench_decode`` (a
    four-step scan of 256 x 512 matmuls) between ``walk``,
    ``decode_dispatch`` and ``token_read`` spans inside a ``window`` span,
    recorded on one TPU v5e."""
    events = tr.events_from_file(str(DATA / "probe.xplane.pb"))
    r = tr.reduce(events, Spans.NAMES)
    assert r.n_devices == 1
    dec = r.program("bench_decode")
    assert dec["calls"] == 3
    # a module's span holds its operations and the gaps between them
    assert 0 < r.busy_s <= dec["s"] <= r.window_s
    assert dec["collective_s"] == 0
    idle = dict(r.idle_by_span)
    # the host sleeps 2 ms in every walk with nothing on the device; a gap
    # is labelled by the span open at its midpoint
    assert "walk" in idle
    assert sum(idle.values()) >= 3 * 2e-3
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


def test_reading_only_what_reduce_reads_reduces_alike():
    """Read with the span names, the recorded trace keeps its device
    operations (by their HLO names), modules and spans, and drops the
    rest, and reduces exactly as the whole trace does."""
    path = str(DATA / "probe.xplane.pb")
    whole = tr.events_from_file(path)
    kept = tr.events_from_file(path, Spans.NAMES)
    assert 0 < len(kept) < len(whole)
    assert {e.line for e in kept if tr.DEVICE_PLANE.match(e.plane)} == {
        tr.OPS_LINE, tr.MODULES_LINE}
    assert all(e.name in Spans.NAMES for e in kept
               if not tr.DEVICE_PLANE.match(e.plane))
    assert all(" = " not in e.name for e in kept)
    assert tr.reduce(kept, Spans.NAMES) == tr.reduce(whole, Spans.NAMES)


def test_a_trace_that_lost_calls_is_refused():
    """One device ran one prefill and one decode step: a window that
    dispatched a second step lost events from the trace."""
    from benchmarks.chip.engines.waves import Wave
    from benchmarks.chip.harness import check_trace_whole
    red = tr.reduce([
        ev(HOST, "python", "window", 0, 100),
        ev(D0, "XLA Modules", "jit_bench_prefill(3)", 10, 20),
        ev(D0, "XLA Modules", "jit_bench_decode(17)", 40, 10),
    ], Spans.NAMES)

    class Record:
        waves = [Wave([0], 0.0, steps=1)]
    check_trace_whole(red, Record)
    Record.waves = [Wave([0], 0.0, steps=2)]
    with pytest.raises(RuntimeError, match="lost events"):
        check_trace_whole(red, Record)
