"""The warm-up has to load every program that the window's queries reach,
so that nothing compiles or loads inside the measured window.  Lane
compaction builds programs for each pair of lane counts it moves
between, which a warm-up of distinct rollouts does not all reach."""

import jax
import pytest

from perfbench.lib import cell, workload

# job count of its own, so that no other test has compiled these shapes
SMALL = dict(n_jobs=40, min_iters=40, max_iters=160, horizon_s=60.0)


def small_config():
    cfg = workload.load_config("paper")
    cfg.update(SMALL)
    cfg["scenario_overrides"] = dict(SMALL)
    return cfg


@pytest.mark.parametrize("lanes", [32, 256, 24])
def test_compactions_cover_every_halving(lanes):
    moves = cell.compactions(lanes)
    f = lanes
    while f > 1:
        t = 1 << ((f - 1).bit_length() - 1)
        assert (f, t) in moves
        f = t
    for f, t in moves:
        assert t < f and t & (t - 1) == 0
    for f in (4, 8, 16):
        assert all((f, t) in moves for t in (1, 2, 4, 8) if t < f // 2)


def test_window_loads_nothing_after_warm_up():
    cfg = small_config()
    traffic = {"lanes": 16, "sample_lanes": 16}
    program = cell.Program(cfg)
    counter = cell.CompileCounter(jax.monitoring)
    errors = []
    try:
        warm = cell.warm_up(program, cfg, traffic, counter)
        assert [q for q, _ in warm][1:] == [
            f"{f}>{t}" for f, t in cell.compactions(16)]
        before = counter.count
        for q in range(4):
            recs, _ = cell.run_query(
                program, cell.query_seeds(2**31 + 5, q, 16), errors)
            assert recs is not None, errors
        loaded = counter.count - before
    finally:
        counter.close()
    assert loaded == 0
