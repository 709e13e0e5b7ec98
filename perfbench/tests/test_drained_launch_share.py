"""``metrics/drained_launch_share.py`` on hand-made query records."""

from types import SimpleNamespace as NS

import pytest

from perfbench.metrics import drained_launch_share


def ctx(*queries):
    return NS(records=list(queries))


def rec(chunks, drained=None):
    r = NS(chunks=chunks, n_jobs=160)
    if drained is not None:
        r.drained_launches = drained
    return r


def test_one_value_per_query():
    # two queries of 4 rollouts, each rollout carrying its batch's counters
    q1 = [rec(11, 2)] * 4
    q2 = [rec(21, 3)] * 4
    assert drained_launch_share.read(ctx(q1, q2)) == pytest.approx(
        100.0 * 5 / 30)


def test_skips_a_query_that_raised():
    assert drained_launch_share.read(ctx(None, [rec(5, 0)])) == 0.0


@pytest.mark.parametrize("queries", [
    ([rec(178)],),  # a program without the counter
    (None,),
    ([rec(1, 0)],),  # one launch: none after the first
])
def test_nothing_to_read(queries):
    assert drained_launch_share.read(ctx(*queries)) is None
