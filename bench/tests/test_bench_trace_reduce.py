"""The trace reduction on a small synthetic trace: busy union, per-program
device time, idle gaps named by the host span around them."""
import pytest

from bench import trace_reduce as T

DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return T.Event(plane, line, name, float(start), float(dur))


def trace():
    return [
        ev("python", T.WINDOW_SPAN, 100, 1000, plane="/host:CPU"),
        ev("python", "bench.eng_run", 100, 600, plane="/host:CPU"),
        ev("python", "bench.hp_compute", 150, 190, plane="/host:CPU"),
        ev("python", "bench.wait", 700, 400, plane="/host:CPU"),
        ev("python", "unrelated", 0, 5000, plane="/host:CPU"),
        # programs: one before the window (clipped away from busy)
        ev("XLA Modules", "jit_prefill_step(3)", 50, 100),
        ev("XLA Modules", "jit_prefill_step(3)", 200, 100),
        ev("XLA Modules", "jit_serve_step(7)", 400, 50),
        ev("XLA Modules", "jit_serve_step(7)", 500, 50),
        # ops: overlapping pairs merge into one busy interval
        ev("XLA Ops", "%fusion.1 = f32[8]{0} fusion(%p.1)", 50, 100),
        ev("XLA Ops", "%fusion.1 = f32[8]{0} fusion(%p.1)", 200, 60),
        ev("XLA Ops", "dot.2", 240, 60),
        ev("XLA Ops", "fusion.3", 400, 50),
        ev("XLA Ops", "fusion.3", 500, 50),
        # another device plane that is not a chip
        ev("XLA Ops", "x", 100, 1000, plane="/device:TPU_SYSTEM:0"),
    ]


def test_window_comes_from_the_window_span():
    r = T.reduce(trace())
    assert r.window_ns == (100.0, 1100.0)
    assert r.window_s == pytest.approx(1e-6)
    assert r.n_devices == 1


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    r = T.reduce(trace())
    # [100,150) clipped + [200,300) merged + [400,450) + [500,550)
    assert r.busy_ns == pytest.approx(50 + 100 + 50 + 50)
    assert r.busy_s == pytest.approx(250e-9)


def test_programs_count_runs_and_device_time_by_name():
    r = T.reduce(trace())
    assert r.program("prefill_step") == (2, pytest.approx(200e-9))
    assert r.program("serve_step") == (2, pytest.approx(100e-9))
    assert r.program("absent") == (0, 0.0)
    assert T.program_name("jit_serve_step(12)") == "serve_step"
    assert T.program_name("while.3") == "while.3"
    assert T.op_name("%while.3 = (s32[], f32[1]) while(%t)") == "while.3"


def test_idle_gaps_are_named_by_the_innermost_bench_span():
    r = T.reduce(trace())
    # gaps [550,1100) [300,400) [150,200) [450,500), longest first
    assert [(n, round(d * 1e9)) for n, d in r.gaps] == [
        ("bench.wait", 550), ("bench.eng_run", 100),
        ("bench.hp_compute", 50), ("bench.eng_run", 50)]
    assert sum(d for _, d in r.gaps) == pytest.approx(r.window_s - r.busy_s)


def test_modules_stand_in_for_ops_when_a_plane_has_none():
    evs = [e for e in trace() if e.line != "XLA Ops"]
    r = T.reduce(evs)
    assert r.busy_ns == pytest.approx(50 + 100 + 50 + 50)


def test_breakdown_lists_top_ops_and_gaps():
    b = T.breakdown(T.reduce(trace()), top=2)
    assert [n for n, _ in b["device_ops"]] == ["prefill_step/fusion.1",
                                               "serve_step/fusion.3"]
    assert b["device_ops"][0][1] == pytest.approx(160e-9)
    assert len(b["idle_gaps"]) == 2 and b["idle_gaps"][0][0] == "bench.wait"


def test_union_merges_and_drops_empty_intervals():
    assert T.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]


def test_a_trace_with_nothing_to_read_is_an_error():
    with pytest.raises(ValueError):
        T.reduce([ev("python", "other", 0, 1, plane="/host:CPU")])
