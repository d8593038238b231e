"""The ``tick_overhead_ms`` reader: host-phase spans summed per step that
began inside the window, averaged over those steps."""
import pytest

from bench import run as bench_run

MS = 1_000_000


def _span(name, t_ms, dur_ms):
    return {"name": name, "ts_ns": int(t_ms * MS), "dur_ns": int(dur_ms * MS)}


def _rec(spans):
    # steps (index, start, end, decode rows): [0, 10) before the window,
    # [10, 20) and [20, 30) inside it, [40, 50) begins after it closes
    steps = [(0, 0, 10 * MS, 4), (1, 10 * MS, 20 * MS, 4), (2, 20 * MS, 30 * MS, 4),
             (3, 40 * MS, 50 * MS, 4)]
    return {"steps": steps, "spans": spans, "w0": 10 * MS, "w1": 35 * MS}


@pytest.fixture(scope="module")
def reader():
    return bench_run.load_reader("tick_overhead_ms.batch")


def test_sums_the_six_phases_of_steps_in_the_window(reader):
    spans = [
        _span("sched.expire", 1, 1.0),  # in a step before the window
        _span("sched.expire", 10.0, 0.5), _span("sched.admit", 10.5, 0.25),
        _span("sched.prefill", 11, 4.0),  # a program span: not counted
        _span("sched.first_token", 15, 1.0), _span("sched.insert", 16, 0.5),
        _span("sched.decode_prep", 16.5, 0.75), _span("sched.decode", 17.25, 2.0),
        _span("sched.decode_commit", 19.5, 0.5),
        _span("req.decode", 17.25, 2.0),  # a request's stage span: not counted
        _span("sched.expire", 20, 0.125), _span("sched.decode_prep", 21, 1.0),
        _span("sched.decode_commit", 29.9, 0.375),  # begins in the step, ends after
        _span("sched.admit", 32, 5.0),  # between steps: in no step
        _span("sched.expire", 40, 9.0),  # in a step that began after the window
    ]
    first = 0.5 + 0.25 + 1.0 + 0.5 + 0.75 + 0.5
    second = 0.125 + 1.0 + 0.375
    got = reader.read(_rec(spans), "tick_overhead_ms.batch")
    assert got == pytest.approx((first + second) / 2, rel=1e-12)


def test_steps_without_phases_count_as_zero(reader):
    got = reader.read(_rec([_span("sched.admit", 12, 3.0)]), "tick_overhead_ms.chat")
    assert got == pytest.approx(3.0 / 2, rel=1e-12)


@pytest.mark.parametrize("spans", [[], [_span("sched.decode", 12, 3.0),
                                        _span("sched.prefill", 22, 3.0)]],
                         ids=["no_spans", "program_spans_only"])
def test_none_without_phase_spans(reader, spans):
    assert reader.read(_rec(spans), "tick_overhead_ms.batch") is None
    assert reader.read({"w0": 0, "w1": 1}, "tick_overhead_ms.batch") is None


def test_both_cells_find_the_reader():
    for name in ("tick_overhead_ms.batch", "tick_overhead_ms.chat"):
        assert bench_run.reader_path(name).endswith("tick_overhead_ms.py")
