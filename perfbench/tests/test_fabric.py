"""A configuration that states a two-tier fabric (``data/fabric.json``: 8
servers x 4 GPUs, racks of 2 servers behind 3x oversubscribed uplinks,
LWF placement) runs through the harness with no edit to its files: the
program and the plain reference agree on it bit for bit, a reference that
ignores the uplinks does not, and ``cell.measure`` takes it with a
reference module and a per-layer reader of its own.  The reference is
pinned on ``paper`` to the values it gave before it modelled fabrics."""

import functools
import hashlib
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.lib import cell, check, reference, trace, workload

FABRIC = workload.ROOT / "tests" / "data" / "fabric.json"
SEEDS = [3, 17, 2**31 + 9, 4_000_000_001]


def fabric_config(**changes):
    return {**workload.load_json(FABRIC), **changes}


def lanes_of(cfg, seeds):
    return [workload.job_arrays(workload.generate(cfg, s)) for s in seeds]


def program_rows(cfg, seeds):
    return [(r.n_finished, r.avg_jct, r.makespan)
            for r in cell.Program(cfg).query(seeds)]


def register(monkeypatch, package, name, **attrs):
    """A module ``perfbench.<package>.<name>`` that exists only for the
    test, found by the harness's own lookup by name."""
    module = types.ModuleType(f"perfbench.{package}.{name}")
    module.__dict__.update(attrs)
    monkeypatch.setitem(sys.modules, module.__name__, module)
    return name


@pytest.mark.parametrize("policy", ["ada", "srsf2"])
def test_program_matches_reference(policy):
    cfg = fabric_config(policy=policy)
    n_nics = cfg["n_servers"]
    uplink_in_flight = []

    def watching_gate(c, m):
        busy = (c["loads"][:, n_nics:] & c["active"][:, None]).any()
        jax.debug.callback(lambda b: uplink_in_flight.append(bool(np.any(b))),
                           busy)
        return reference.threshold_gate(c, m)

    ref = reference.simulate(lanes_of(cfg, SEEDS), cfg,
                             gates={policy: watching_gate})
    assert check.lanes_off(program_rows(cfg, SEEDS), ref) == 0
    assert any(uplink_in_flight)  # some transfer crossed a rack uplink


def test_reference_ignoring_uplinks_is_off(monkeypatch):
    """A planted fault: a reference that models only the NICs."""

    def nics_only(lanes, cfg, **kw):
        return reference.simulate(lanes, {**cfg, "topology": "nic"}, **kw)

    name = register(monkeypatch, "references", "nics_only", simulate=nics_only)
    cfg = fabric_config(reference=name)
    ref = workload.reference_of(cfg)(lanes_of(cfg, SEEDS), cfg)
    assert check.lanes_off(program_rows(cfg, SEEDS), ref) >= 1


def test_unknown_placement_raises():
    cfg = fabric_config(placement="lwf_rack")
    with pytest.raises(ValueError, match="placements"):
        reference.simulate(lanes_of(cfg, SEEDS[:1]), cfg)


# (seed, float type) -> (jobs finished, average JCT, makespan, JCTs'
# digest) of the reference on ``paper`` at a small cut, as it gave them
# while it modelled one NIC domain per server and nothing else
PAPER_SMALL = dict(n_jobs=48, min_iters=100, max_iters=300, horizon_s=60.0)
PINNED = {
    (5, "float32"): (48, 54.41250185171763, 294.95001220703125,
                     "e3c4bb6a340e633b"),
    (2**31 + 13, "float32"): (48, 87.84479345877965, 442.8999938964844,
                              "01da1eb0a26ba09b"),
    (4_000_000_007, "float32"): (48, 54.01562730471293, 367.20001220703125,
                                 "51b8332abc8d3680"),
    (5, "bfloat16"): (48, 54.41250185171763, 294.95001220703125,
                      "e3c4bb6a340e633b"),
    (2**31 + 13, "bfloat16"): (48, 87.75521069765091, 444.6000061035156,
                               "48d2050ab584e663"),
    (4_000_000_007, "bfloat16"): (48, 54.01562730471293, 367.20001220703125,
                                  "51b8332abc8d3680"),
}


@pytest.fixture(scope="module")
def paper_small():
    cfg = {**workload.load_config("paper"), **PAPER_SMALL}
    seeds = sorted({s for s, _ in PINNED})
    lanes = lanes_of(cfg, seeds)
    out = {}
    for ftype in (jnp.float32, jnp.bfloat16):
        for s, r in zip(seeds, reference.simulate(lanes, cfg, ftype=ftype)):
            jct = np.asarray(r["jct"], np.float32).tobytes()
            out[s, ftype.__name__] = (*check.summarize(r["jct"], r["finished"]),
                                      float(r["makespan"]),
                                      hashlib.sha256(jct).hexdigest()[:16])
    return out


@pytest.mark.parametrize("key", sorted(PINNED))
def test_reference_pinned_on_paper(paper_small, key):
    assert paper_small[key] == PINNED[key]


def test_measure_takes_a_new_configuration_and_reader(monkeypatch):
    """``cell.measure`` runs the fabric configuration with a reference
    module that it names and a per-layer reader that reads the program's
    counters and spans, none of them known to the harness's files."""
    compared = []

    def counting(lanes, cfg, **kw):
        compared.append(len(lanes))
        return reference.simulate(lanes, cfg, **kw)

    def read(ctx):
        (recs,) = ctx.records
        assert ctx.spans.table["fluid.build"].count == 1
        return sum(r.compactions for r in recs) / sum(r.chunks for r in recs)

    ref = register(monkeypatch, "references", "counting", simulate=counting)
    reader = register(monkeypatch, "metrics", "compactions_per_chunk", read=read)
    # a CPU trace holds no device plane, which the device reduction refuses
    monkeypatch.setattr(trace, "reduce", lambda pd, span: None)
    res = cell.measure(
        {"chips": 1}, fabric_config(reference=ref),
        {"lanes": 8, "sample_lanes": 8},
        {"end_to_end": [], "per_layer": [(reader, "1/chunk"),
                                         ("chunks_per_query", "chunks")]},
        seed=2**31 + 3, seconds=0.5, trace=True, t_start=time.perf_counter(),
        require_accelerator=False,
    )
    assert res["correct"], res["checks"]
    assert compared == [8]
    assert 0 < res["metrics"][reader]["value"] < 1
    assert res["metrics"]["chunks_per_query"]["value"] >= 1


def test_partial_reference_module(monkeypatch):
    """The factoring a later reference module uses: the base with one rule
    replaced, here LWF's rank under another name."""
    name = register(
        monkeypatch, "references", "renamed",
        simulate=functools.partial(reference.simulate,
                                   ranks={"lwf_alias": reference.lwf_rank}))
    cfg = fabric_config(reference=name, placement="lwf_alias")
    base = fabric_config()
    got = workload.reference_of(cfg)(lanes_of(cfg, SEEDS[:2]), cfg)
    want = reference.simulate(lanes_of(base, SEEDS[:2]), base)
    assert [float(r["makespan"]) for r in got] == [
        float(r["makespan"]) for r in want]
