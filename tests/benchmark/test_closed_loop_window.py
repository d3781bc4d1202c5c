"""The measured window on clients that only count: each client warms its own
connection before the window opens, a failed warm-up ends the run, a failed
timed submission is a record; and the window's arithmetic: ``rows_per_s``,
``query_s`` and ``query_max_s`` are over all the work and all the time of the
window, a stall between queries counts, a failed query counts its timeout."""

import threading
import time

import pytest

import benchlib  # noqa: F401  (puts the benchmark's library on the path)
from rtbench import loader, window


class FakeClient:
    last_fell_back, last_metrics = (), {}

    def __init__(self, log, fail_at=None, seconds=0.01):
        self.log, self.fail_at, self.seconds = log, fail_at, seconds
        self.calls = 0

    def collect(self, plan):
        self.calls += 1
        self.log.append((id(self), plan, time.perf_counter()))
        if self.calls == self.fail_at:
            raise OSError("connection lost")
        time.sleep(self.seconds)
        return plan

    def close(self):
        pass


@pytest.mark.parametrize("clients,warm_passes,warm_seconds", [
    (1, 1, 0.0), (3, 2, 0.0), (2, 1, 0.07), (2, 3, 0.01)])
def test_each_client_warms_its_own_connection_first(clients, warm_passes,
                                                    warm_seconds):
    log, made, opened = [], [], []

    def connect():
        made.append(FakeClient(log))
        return made[-1]

    records, first, last, warm = window.run(
        connect, ["a", "b"], [1, 1], clients, 0.2, seed=7,
        warm_passes=warm_passes, warm_seconds=warm_seconds,
        opened=lambda: opened.append(time.perf_counter()))
    assert len(made) == clients and len(opened) == 1
    before = [e for e in log if e[2] < opened[0]]
    # every plan on every connection, warm_passes times and again until the
    # connection is warm_seconds old (a pass of two 10 ms queries), before
    # the window
    for c in made:
        mine = [(p, t) for i, p, t in before if i == id(c)]
        passes = len(mine) // 2
        assert [p for p, _ in mine] == ["a", "b"] * passes
        assert opened[0] - mine[0][1] >= warm_seconds
        if warm_seconds == 0.07:
            assert passes >= 3          # the age asked for more than one
        else:
            assert passes == warm_passes
    assert len(warm) == len(before)
    # and no warm-up submission is a record
    assert len(records) == len(log) - len(before) >= clients
    assert first == records[0].submit >= opened[0]
    assert last == max(r.reply for r in records) > first
    assert {r.client for r in records} == set(range(clients))


def test_a_failed_warm_up_ends_the_run_and_no_client_hangs():
    made = []

    def connect():
        made.append(FakeClient([], fail_at=1 if not made else None))
        return made[-1]

    with pytest.raises(RuntimeError, match="warm-up"):
        window.run(connect, ["a"], [1], 3, 0.2, seed=1)
    assert threading.active_count() < 10


def test_a_failed_timed_submission_is_a_record_on_a_new_connection():
    made = []

    def connect():
        made.append(FakeClient([], fail_at=3 if not made else None))
        return made[-1]

    seen = []
    records, _, _, _ = window.run(
        connect, ["a"], [1], 1, 0.1, seed=1,
        between=lambda mine: seen.append(None if mine is None
                                         else len(mine)))
    assert records[1].error == "OSError: connection lost"
    assert records[0].error is None and records[2].error is None
    assert len(made) == 2
    # client 0 is asked before each timed submission, and once at the end
    assert seen == list(range(len(records))) + [None]


def _run(spans, rows=(1000,), clients=1, timeout_s=240.0, queries=None,
         errors=()):
    """A run as ``run.py`` hands it to the metric readers, from
    ``(submit, reply)`` pairs; ``errors`` holds the indices that failed."""
    records = []
    for i, (submit, reply) in enumerate(spans):
        r = window.Record(i % clients, (queries or [0] * len(spans))[i],
                          submit)
        r.reply = reply
        r.error = "TimeoutError: timed out" if i in errors else None
        records.append(r)
    first = min(s for s, _ in spans)
    last = max(e for _, e in spans)
    return {"records": records,
            "done": [r for r in records if r.error is None],
            "seconds": [timeout_s if r.error else r.seconds
                        for r in records],
            "window_s": last - first, "scanned_rows": list(rows),
            "clients": clients, "timeout_s": timeout_s, "setup_s": 12.5}


def _read(name, run):
    return loader.metric(name).read(run)


def test_rate_and_seconds_per_query_are_over_the_whole_window():
    run = _run([(100.0, 130.0), (130.0, 160.0)], rows=(6_000_000,))
    assert _read("rows_per_s", run) == pytest.approx(12_000_000 / 60.0)
    assert _read("query_s", run) == pytest.approx(30.0)
    assert _read("query_max_s", run) == pytest.approx(30.0)
    assert _read("setup_s", run) == 12.5


def test_a_stall_between_queries_counts():
    # the same two 30 s queries with 4 s of stall between them: no query
    # is slower, and both window metrics are worse
    run = _run([(100.0, 130.0), (134.0, 164.0)], rows=(6_000_000,))
    assert _read("query_max_s", run) == pytest.approx(30.0)
    assert _read("query_s", run) == pytest.approx(32.0)
    assert _read("rows_per_s", run) == pytest.approx(12_000_000 / 64.0)


def test_a_stall_inside_one_query_shows_in_all_three():
    run = _run([(0.0, 30.0), (30.0, 66.0)], rows=(6_000_000,))
    assert _read("query_max_s", run) == pytest.approx(36.0)
    assert _read("query_s", run) == pytest.approx(33.0)
    assert _read("rows_per_s", run) == pytest.approx(12_000_000 / 66.0)


def test_each_query_adds_the_rows_its_own_plan_scans():
    run = _run([(0.0, 1.0), (1.0, 3.0), (3.0, 4.0)], rows=(100, 700),
               queries=[0, 1, 0])
    assert _read("rows_per_s", run) == pytest.approx(900 / 4.0)
    assert _read("query_s", run) == pytest.approx(4.0 / 3)
    assert _read("query_max_s", run) == pytest.approx(2.0)


def test_seconds_per_query_are_client_seconds():
    # two closed-loop clients side by side, four queries in 20 s: each
    # user waited 10 s a query, and the rate is the server's
    run = _run([(0.0, 10.0), (0.0, 9.0), (10.0, 20.0), (9.0, 19.0)],
               rows=(50,), clients=2)
    assert _read("query_s", run) == pytest.approx(10.0)
    assert _read("rows_per_s", run) == pytest.approx(200 / 20.0)


@pytest.mark.parametrize("reply", [250.0, 12.0])
def test_a_failed_query_counts_its_timeout_and_completes_nothing(reply):
    """Timed out after its 240 s, or refused after 2 s: either way it adds
    no rows, reads as the timeout in ``query_max_s`` and is charged the
    timeout in ``query_s``."""
    run = _run([(0.0, 10.0), (10.0, reply)], rows=(1000,), errors={1})
    assert len(run["done"]) == 1
    assert _read("query_max_s", run) == 240.0
    assert _read("query_s", run) == pytest.approx(10.0 + 240.0)
    assert _read("rows_per_s", run) == pytest.approx(1000 / reply)


def test_a_window_that_completed_nothing_reads_as_nothing_not_zero():
    run = _run([(0.0, 5.0)], errors={0})
    assert _read("rows_per_s", run) is None
    assert _read("query_s", run) is None
    assert _read("query_max_s", run) == 240.0
