"""The paper's Section V-A trace (arXiv 2002.10105): jobs arriving uniformly
over the horizon on whole seconds, a fixed GPU-count mix, uniform
iterations, and a model drawn uniformly from Table III.

Keys read from the configuration: ``n_jobs``, ``horizon_s``, ``min_iters``,
``max_iters`` and ``gpu_distribution`` (pairs of GPUs per job and jobs per
160).  Returns ``(arrival, n_gpus, iterations, model_index)`` per job, in
arrival order.
"""

from __future__ import annotations

import random


def jobs(seed: int, cfg: dict, n_models: int) -> list:
    rng = random.Random(seed)
    n_jobs = cfg["n_jobs"]
    dist = [tuple(p) for p in cfg["gpu_distribution"]]
    total = sum(c for _, c in dist)
    gpu_counts = []
    for gpus, count in dist:
        scaled = max(1, round(count * n_jobs / total)) if count else 0
        gpu_counts.extend([gpus] * scaled)
    rng.shuffle(gpu_counts)
    gpu_counts = gpu_counts[:n_jobs]
    gpu_counts += [1] * (n_jobs - len(gpu_counts))
    out = []
    for k in range(n_jobs):
        arrival = float(int(rng.uniform(1.0, cfg["horizon_s"])))
        iters = rng.randint(cfg["min_iters"], cfg["max_iters"])
        model = rng.choice(range(n_models))  # draws as choice() over the list
        out.append((arrival, k, gpu_counts[k], iters, model))
    out.sort(key=lambda j: (j[0], j[1]))
    return [(a, g, i, m) for a, _, g, i, m in out]

