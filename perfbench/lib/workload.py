"""Configurations, traffic mixes and the benchmark's own job generators.

A configuration is ``configs/<name>.json``; it names a generator
(``generators/<generator>.py``) that is the benchmark's own copy of the
program's job generator, so the inputs of the reference never come from
the program, and may name its own plain reference
(``references/<reference>.py``, :func:`reference_of`).
:func:`check_program_jobs` is the workload guard: at set-up the program's
scenario registry has to yield, seed for seed, the jobs and the cluster,
fabric included, that the configuration states, or the run stops.
"""

from __future__ import annotations

import hashlib
import importlib
import json
from pathlib import Path

import numpy as np

from perfbench.lib import fabric

ROOT = Path(__file__).resolve().parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return load_json(ROOT / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return load_json(ROOT / "traffic" / f"{name}.json")


def model_table() -> list:
    return load_json(ROOT / "generators" / "table_iii.json")["models"]


def generate(cfg: dict, seed: int) -> list:
    """The configuration's jobs for one seed, as ``(arrival, n_gpus,
    iterations, model_index)`` in arrival order."""
    gen = importlib.import_module(f"perfbench.generators.{cfg['generator']}")
    return gen.jobs(seed, cfg, len(model_table()))


def reference_of(cfg: dict):
    """``simulate(lanes, cfg, ftype=..., block=...)`` of the
    configuration's plain reference: ``references/<reference>.py`` where
    the configuration names one, else :mod:`perfbench.lib.reference`."""
    name = cfg.get("reference")
    module = (f"perfbench.references.{name}" if name is not None
              else "perfbench.lib.reference")
    return importlib.import_module(module).simulate


def job_arrays(jobs: list) -> dict:
    """Struct-of-arrays of one rollout's jobs, float32 as the model states."""
    models = model_table()
    return {
        "arrival": np.asarray([j[0] for j in jobs], np.float32),
        "n_gpus": np.asarray([j[1] for j in jobs], np.int32),
        "iters": np.asarray([j[2] for j in jobs], np.float32),
        "t_iter": np.asarray(
            [models[j[3]]["t_f"] + models[j[3]]["t_b"] for j in jobs], np.float32
        ),
        "msg_bytes": np.asarray(
            [models[j[3]]["size_bytes"] for j in jobs], np.float32
        ),
    }


def digest(rows) -> str:
    return hashlib.sha256(repr(list(rows)).encode()).hexdigest()[:16]


def own_rows(cfg: dict, seed: int) -> list:
    models = model_table()
    return [
        (a, g, i, models[m]["name"], models[m]["size_bytes"],
         models[m]["t_f"], models[m]["t_b"])
        for a, g, i, m in generate(cfg, seed)
    ]


def program_rows(scn) -> list:
    return [
        (j.arrival, j.n_gpus, j.iterations, j.model.name, j.model.size_bytes,
         j.model.t_f, j.model.t_b)
        for j in scn.job_list()
    ]


def cluster_row(cfg: dict) -> tuple:
    c = cfg["contention"]
    return (cfg["n_servers"], cfg["gpus_per_server"], c["a"], c["b"],
            c["eta"], tuple(c["server_bandwidth"]), fabric.domains(cfg),
            cfg.get("fusion", "all"), cfg.get("chaos"))


def program_domains(scn) -> tuple:
    """The scenario's contention domains in :func:`fabric.domains`' form;
    no topology is one NIC domain per server."""
    if scn.topology is None:
        return tuple(((s,), 1.0) for s in range(scn.n_servers))
    return tuple((d.servers, float(d.oversub))
                 for d in scn.topology.domains)


def program_cluster_row(scn) -> tuple:
    p = scn.params
    return (scn.n_servers, scn.gpus_per_server, p.a, p.b, p.eta,
            tuple(p.server_bandwidth), program_domains(scn), scn.fusion,
            scn.chaos)


def check_program_jobs(cfg: dict, seeds, get_scenario) -> None:
    """Raise ``RuntimeError`` unless ``get_scenario`` builds, for every
    seed, the configuration's cluster and exactly the jobs the
    benchmark's own generator draws (arrival, GPUs, iterations, model)."""
    for seed in seeds:
        scn = get_scenario(cfg["scenario"], seed=seed, **cfg["scenario_overrides"])
        got, want = program_cluster_row(scn), cluster_row(cfg)
        if got != want:
            raise RuntimeError(
                f"workload guard: scenario {cfg['scenario']!r} seed {seed} "
                f"builds cluster {got}, configuration {cfg['name']!r} "
                f"states {want}"
            )
        got, want = digest(program_rows(scn)), digest(own_rows(cfg, seed))
        if got != want:
            raise RuntimeError(
                f"workload guard: scenario {cfg['scenario']!r} seed {seed} "
                f"generates jobs with digest {got}, the benchmark's copy of "
                f"its generator {want}: the program's generator changed"
            )
