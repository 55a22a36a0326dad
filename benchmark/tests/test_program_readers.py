"""The readers of the program's own records (``program_phase_share``,
``program_count``, ``program_request``) on hand-made iterations and
requests, written through the engine's one instrument with a clock the
test sets: window clipping, the six shares summing to 100, and None once
the ring has wrapped past the window or the program keeps no records."""
import types

import pytest

from benchmark.lib import program, stats
from benchmark.readers import (program_count, program_phase_share,
                               program_request)
from deepspeed_tpu.observability import overlap

PHASES = ("plan", "operands", "enqueue", "device_wait", "apply")
NS = 10**9


@pytest.fixture
def prof(monkeypatch):
    """The process-global profiler, enabled, on a clock the test sets."""
    clock = types.SimpleNamespace(t=0)
    monkeypatch.setattr(overlap, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: clock.t))
    p = overlap.get_overlap_profiler()
    default = p._its.capacity
    p.reset()
    p.configure(enabled=True)
    p.clock = clock
    yield p
    del p.clock
    p.configure(enabled=False, capacity=default)
    p.reset()


def iteration(p, begin_s, dispatches):
    """One iteration from ``begin_s``: per dispatch the five phase times
    (s) and ``(decode_rows, chunk_rows, rows_computed)``."""
    p.clock.t = int(begin_s * NS)
    p.begin()
    for k, (times, rows) in enumerate(dispatches):
        for phase, seconds in enumerate(times):
            p.clock.t += int(seconds * NS)
            if phase + 1 < len(PHASES):
                p.mark(phase + 1)
        p.count_dispatch(*rows)
        if k + 1 < len(dispatches):
            p.mark(overlap.PLAN)
    p.end()


def request(p, submit, admit, first, finish):
    p.note_request(types.SimpleNamespace(
        submit_time=submit, admit_time=admit, first_token_time=first,
        finish_time=finish))


ONE = ((0.01, 0.02, 0.01, 0.95, 0.01), (6, 0, 40))      # 1.0 s


def test_phase_shares_clip_to_the_window_and_sum_to_100(prof):
    iteration(prof, 0.0, [ONE])                     # ends 1.0: before w0
    iteration(prof, 1.5, [ONE])                     # ends 2.5
    iteration(prof, 3.0, [ONE, ((0.0, 0.02, 0.01, 0.46, 0.01),
                                (6, 16, 40))])      # ends 4.5
    iteration(prof, 5.0, [ONE])                     # ends 6.0: after w1
    obs = {"window": (1.0, 4.5)}
    share = {p: program_phase_share.read(obs, p)
             for p in PHASES + ("outside",)}
    # the span runs from 1.5 to 4.5; the caller held 0.5 s between steps
    assert share["outside"] == pytest.approx(100 * 0.5 / 3.0)
    assert share["device_wait"] == pytest.approx(100 * (0.95 + 1.41) / 3.0)
    assert share["operands"] == pytest.approx(100 * 0.06 / 3.0)
    assert share["plan"] == pytest.approx(100 * 0.02 / 3.0)
    assert sum(share.values()) == pytest.approx(100.0)
    # an iteration belongs to the window its END lies in: (w0, w1]
    assert program_phase_share.read({"window": (2.5, 4.5)}, "outside") == \
        pytest.approx(0.0)
    assert program_phase_share.read({"window": (6.0, 9.0)}, "plan") is None


def test_counts_second_dispatches_and_useful_rows(prof):
    iteration(prof, 0.0, [ONE])
    iteration(prof, 1.0, [ONE, ((0.0, 0.0, 0.0, 0.5, 0.0), (0, 16, 40))])
    iteration(prof, 3.0, [ONE])
    iteration(prof, 4.0, [((0.1,) * 5, (24, 256, 280))])
    obs = {"window": (1.0, 4.0)}           # ends 1.0, 2.5, 4.0, 4.5
    assert program_count.read(obs, "second_dispatch") == \
        pytest.approx(100 / 2)
    assert program_count.read(obs, "useful_rows") == \
        pytest.approx(100 * (6 + 16 + 6) / 120)
    every = {"window": (0.0, 10.0)}
    assert program_count.read(every, "second_dispatch") == \
        pytest.approx(100 / 4)
    assert program_count.read(every, "useful_rows") == \
        pytest.approx(100 * (6 + 6 + 16 + 6 + 280) / (160 + 280))
    with pytest.raises(ValueError):
        program_count.read(every, "rows")


def test_request_stamps_by_submit_in_the_window(prof):
    request(prof, 0.5, 0.6, 0.9, 2.0)        # submitted before w0
    request(prof, 1.10, 1.15, 1.45, 3.0)     # waits 50, prefill 300
    request(prof, 1.20, 1.30, 1.50, 2.5)     # 100, 200
    request(prof, 2.00, 2.01, 2.16, 4.0)     # 10, 150
    request(prof, 5.5, 5.6, 5.7, 6.0)        # after w1
    obs = {"window": (1.0, 5.0)}
    wait = dict(start="submit_time", end="admit_time")
    fill = dict(start="admit_time", end="first_token_time")
    assert program_request.read(obs, q=0.5, **wait) == pytest.approx(50.0)
    assert program_request.read(obs, q=1.0, **wait) == pytest.approx(100.0)
    assert program_request.read(obs, q=0.5, **fill) == pytest.approx(200.0)
    # a request cancelled in the queue never reached the later stamp
    request(prof, 2.5, None, None, 2.6)
    request(prof, 2.6, None, None, 2.7)
    assert program_request.read(obs, q=0.5, **wait) == pytest.approx(100.0)
    assert program_request.read(obs, q=1.0, **wait) == stats.INF_MS
    assert program_request.read({"window": (6.0, 7.0)}, q=0.5,
                                **wait) is None


def test_a_wrapped_ring_reads_as_nothing(prof):
    prof.configure(enabled=True, capacity=3)
    for k in range(4):                              # ends 1, 2, 3, 4
        iteration(prof, float(k), [ONE])
        request(prof, k + 0.5, k + 0.6, k + 0.7, k + 1.0)
    lost = {"window": (0.5, 4.0)}                   # the first is gone
    held = {"window": (2.0, 4.0)}
    for obs, gone in ((lost, True), (held, False)):
        got = (program_phase_share.read(obs, "plan"),
               program_count.read(obs, "useful_rows"),
               program_request.read(obs, "submit_time", "admit_time", 0.5))
        assert all(g is None for g in got) if gone else \
            all(g is not None for g in got)
    assert program_count.read(held, "useful_rows") == pytest.approx(15.0)


def test_a_program_without_the_records_reads_as_nothing(prof, monkeypatch):
    """The parent commit's profiler has no ``iterations`` / ``requests``:
    every new metric is left out of its line, and nothing raises."""
    iteration(prof, 0.0, [ONE])
    bare = types.SimpleNamespace(last=lambda: None)
    monkeypatch.setattr(program, "get_overlap_profiler", lambda: bare)
    obs = {"window": (0.0, 9.0)}
    assert program.records(obs, "iterations") is None
    assert program_phase_share.read(obs, "plan") is None
    assert program_count.read(obs, "second_dispatch") is None
    assert program_request.read(obs, "submit_time", "admit_time", 0.5) \
        is None


def test_training_records_are_not_serving_iterations(prof):
    prof.observe("train", total_s=1.0, enqueue_s=0.1, wait_s=0.8, t0_ns=NS)
    assert program.records({"window": (0.0, 9.0)}, "iterations") is None
