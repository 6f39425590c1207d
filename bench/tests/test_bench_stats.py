"""Tail arithmetic: unfinished requests count at their age at the
close, inter-token gaps come from token stamps."""
import pytest

from bench import stats
from bench.drive import Log, Req


def req(index, due, stamps, finish=None, submit=None):
    r = Req(index, index, 0, due, [1, 2, 3], len(stamps), None)
    r.stamps = list(stamps)
    r.finish_t = finish
    r.submit_t = due if submit is None else submit
    return r


def log(reqs, unsubmitted=(), open_t=10.0, close_t=20.0):
    c = {"admissions": 0}
    return Log(list(reqs), [], list(unsubmitted), open_t, close_t, c, c, [])


def test_ttft_counts_unfinished_at_age():
    lg = log([req(0, 11.0, [11.5, 11.7], finish=11.7),
              req(1, 15.0, []),                       # no first token
              req(2, 9.0, [9.5]),                     # due before open
              req(3, 19.0, [20.5])],                  # first token late
             unsubmitted=[Req(-1, 9, 0, 18.0, [], 0, None)])
    assert sorted(stats.ttft_samples(lg)) == pytest.approx(
        [0.5, 1.0, 2.0, 5.0])
    assert sorted(stats.turn_samples(lg)) == pytest.approx(
        [0.7, 1.0, 2.0, 5.0])
    assert stats.summary(lg)["due_in_window"] == 4
    assert stats.summary(lg)["never_submitted"] == 1


def test_itl_from_stamps_ending_in_window():
    lg = log([req(0, 5.0, [9.0, 10.5, 10.6, 12.0, 21.0])])
    assert stats.itl_samples(lg) == pytest.approx([1.5, 0.1, 1.4])
    assert stats.tokens_in_window(lg) == 3


def test_p95_interpolates_and_is_none_when_empty():
    assert stats.p95([]) is None
    assert stats.p95(list(range(101))) == pytest.approx(95.0)
    assert stats.p95([1.0, 2.0]) == pytest.approx(1.95)


def test_lateness():
    lg = log([req(0, 11.0, [], submit=11.25), req(1, 12.0, [], submit=12.0)])
    assert sorted(stats.lateness(lg)) == pytest.approx([0.0, 0.25])


def test_in_flight_counts_due_and_unfinished():
    lg = log([req(0, 11.0, [11.5], finish=12.0),
              req(1, 11.5, []),                       # never finished
              req(2, 13.0, [13.2], finish=13.5)],
             unsubmitted=[Req(-1, 9, 0, 12.5, [], 0, None)])
    assert [stats.in_flight(lg, t) for t in (10.0, 11.0, 11.9, 12.0,
                                              12.5, 13.2, 14.0)] == \
        [0, 1, 2, 1, 2, 3, 2]
