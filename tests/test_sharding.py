"""Sharding-rule unit tests (pure logic — no fake devices) plus a
subprocess-based mini dry-run on 8 forced host devices that also validates
the scan-body cost correction against a fully-unrolled compile."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

# Pure-logic tests use a Mesh built lazily inside a subprocess-safe guard:
# constructing an abstract mesh for spec computation doesn't need devices —
# but jax.make_mesh does, so we use jax.sharding.AbstractMesh.
import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.sharding.rules import ShardingStrategy, spec_for_param


def mesh2d():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh3d():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestSpecForParam:
    def test_tp_shards_heads(self):
        spec = spec_for_param(
            ("embed", "q_heads", "head_dim"), (4096, 32, 128), mesh2d(),
            ShardingStrategy("tp"),
        )
        assert spec == P(None, "model", None)

    def test_divisibility_guard_drops_axis(self):
        """yi-9b: 4 kv heads on a 16-way model axis stay replicated."""
        spec = spec_for_param(
            ("embed", "kv_heads", "head_dim"), (4096, 4, 128), mesh2d(),
            ShardingStrategy("tp"),
        )
        assert spec == P(None, None, None)

    def test_mesh_axis_used_at_most_once(self):
        """MoE weights (experts, embed, ffn): experts win, ffn dropped."""
        spec = spec_for_param(
            ("experts", "embed", "ffn"), (128, 7168, 4864), mesh2d(),
            ShardingStrategy("tp"),
        )
        assert spec == P("model", None, None)

    def test_fsdp_adds_data_axis(self):
        spec = spec_for_param(
            ("embed", "ffn"), (7168, 4864), mesh2d(), ShardingStrategy("fsdp")
        )
        assert spec == P("data", "model")

    def test_fsdp_multipod_uses_both_axes(self):
        spec = spec_for_param(
            ("embed", "ffn"), (7168, 4864), mesh3d(), ShardingStrategy("fsdp")
        )
        assert spec == P(("pod", "data"), "model")

    def test_dp_replicates_everything(self):
        spec = spec_for_param(
            ("vocab", "embed"), (50280, 768), mesh2d(), ShardingStrategy("dp")
        )
        assert spec == P(None, None)

    def test_vocab_padded_shards(self):
        from repro.models.config import pad_to, VOCAB_PAD_MULTIPLE

        v = pad_to(256206, VOCAB_PAD_MULTIPLE)
        spec = spec_for_param(("vocab", "embed"), (v, 1024), mesh2d(), ShardingStrategy("tp"))
        assert spec == P("model", None)


class TestBatchAxes:
    def test_batch_specs(self):
        from repro.sharding.rules import batch_spec_axes

        assert batch_spec_axes(mesh2d(), 256) == ("data",)
        assert batch_spec_axes(mesh3d(), 256) == ("pod", "data")
        assert batch_spec_axes(mesh3d(), 16) == ("pod",)  # 32 doesn't divide 16
        assert batch_spec_axes(mesh2d(), 1) is None
        assert batch_spec_axes(mesh2d(), 256, include_model=True) == ("data", "model")


MINI_DRYRUN = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import dataclasses
    import jax

    from repro.configs import get_config
    from repro.launch.dryrun import Profile, run_combo, with_n_blocks, _build_and_lower, _compile_and_analyze
    from repro.models.config import InputShape
    from repro.models.lm import LM, RunFlags

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(2, 4)
    shape = InputShape("mini_train", seq_len=64, global_batch=4, kind="train")
    profile = Profile(strategy="tp", remat="none", q_chunk=32)

    cfg = dataclasses.replace(
        get_config("llama3.2-1b", reduced=True), n_layers=6)

    flags = RunFlags(remat="none", q_chunk=32)
    full = _compile_and_analyze(_build_and_lower(cfg, shape, mesh, profile, flags))
    small = with_n_blocks(cfg, 4)
    u1 = _compile_and_analyze(_build_and_lower(small, shape, mesh, profile,
                                               dataclasses.replace(flags, scan_unroll=1)))
    u2 = _compile_and_analyze(_build_and_lower(small, shape, mesh, profile,
                                               dataclasses.replace(flags, scan_unroll=2)))
    delta = u2["cost"]["flops"] - u1["cost"]["flops"]
    corrected = full["cost"]["flops"] + (6 - 1) * delta
    # ground truth: fully unrolled 6-layer model
    unrolled = _compile_and_analyze(_build_and_lower(
        cfg, shape, mesh, profile, dataclasses.replace(flags, scan_unroll=6)))
    print(json.dumps({
        "corrected": corrected,
        "unrolled": unrolled["cost"]["flops"],
        "scanned_raw": full["cost"]["flops"],
        "collectives_found": full["collectives"]["op_counts"],
    }))
    """
)


@pytest.mark.slow
class TestMiniDryrunSubprocess:
    def test_scan_correction_matches_full_unroll(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        out = subprocess.run(
            [sys.executable, "-c", MINI_DRYRUN],
            capture_output=True,
            text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=1200,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        data = json.loads(out.stdout.strip().splitlines()[-1])
        corrected, unrolled = data["corrected"], data["unrolled"]
        # corrected must land within 15% of ground truth, and be much
        # better than the raw scanned number (which counts one body).
        assert abs(corrected - unrolled) / unrolled < 0.15, data
        assert abs(data["scanned_raw"] - unrolled) / unrolled > 0.3, data
        # the partitioned module must actually contain collectives
        assert sum(data["collectives_found"].values()) > 0, data
