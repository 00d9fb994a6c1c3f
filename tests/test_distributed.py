"""Sharding rules + a miniature end-to-end SPMD run on 8 fake devices."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import mesh_axes, spec_for_leaf
from repro.launch.roofline import (
    CellCosts,
    collective_bytes_by_computation,
    extrapolate,
    fused_hbm_bytes,
)

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class _Key:
    def __init__(self, k):
        self.key = k


def _spec(names, shape, mesh):
    path = tuple(_Key(n) for n in names)
    return spec_for_leaf(path, jax.ShapeDtypeStruct(shape, jnp.bfloat16), mesh)


class TestShardingRules:
    def test_mlp_tp_fsdp(self):
        s = _spec(["stack", "period", "mlp", "w_gate"], (6144, 24576), MESH_1POD)
        assert s == P("data", "model")

    def test_multi_pod_fsdp_spans_pod_and_data(self):
        s = _spec(["stack", "mlp", "w_gate"], (6144, 24576), MESH_2POD)
        assert s == P(("pod", "data"), "model")

    def test_stacked_period_params_get_leading_none(self):
        s = _spec(["stack", "period", "attn", "wq"], (10, 5376, 4096), MESH_1POD)
        assert s == P(None, "data", "model")

    def test_indivisible_heads_fall_back(self):
        # 90 columns cannot split 16-way tp -> tp dropped (trailing trim)
        s = _spec(["attn", "wq"], (128, 90), MESH_1POD)
        assert s == P("data")

    def test_indivisible_fsdp_partially_drops(self):
        # 24 % (pod*data=32) != 0 but 24 % pod=2 == 0 -> keep only 'pod'
        s = _spec(["attn", "wq"], (24, 90), MESH_2POD)
        assert s == P("pod")

    def test_moe_expert_rules_match_epspec(self):
        s = _spec(["moe", "w_gate"], (128, 2048, 768), MESH_1POD)
        assert s == P("model", None, "data")
        s = _spec(["moe", "w_down"], (128, 768, 2048), MESH_1POD)
        assert s == P("model", "data")  # trailing None trimmed
        s = _spec(["moe", "shared", "w_gate"], (7168, 2048), MESH_1POD)
        assert s == P(None, "model")

    def test_router_replicated(self):
        assert _spec(["moe", "router"], (2048, 128), MESH_1POD) == P()

    def test_embed(self):
        s = _spec(["embed"], (262144, 5376), MESH_1POD)
        assert s == P("model", "data")

    def test_mesh_axes(self):
        assert mesh_axes(MESH_1POD)["dp"] == ("data",)
        assert mesh_axes(MESH_2POD)["dp"] == ("pod", "data")


class TestRooflineParsers:
    HLO = textwrap.dedent(
        """
        ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
          %p0 = f32[8,16]{1,0} parameter(0)
          %w = bf16[16,32]{1,0} parameter(1)
          %all-gather.1 = bf16[16,128]{1,0} all-gather(%w), replica_groups={{0,1}}
          %dot.1 = f32[8,128]{1,0} dot(%p0, %all-gather.1), lhs_contracting_dims={1}
          %exp = f32[8,128]{1,0} exponential(%dot.1)
          %red = f32[8]{0} reduce(%exp, %c), dimensions={1}
          ROOT %ar = f32[8,16]{1,0} all-reduce(%p0), to_apply=%sum
        }
        """
    )

    def test_collective_bytes(self):
        per = collective_bytes_by_computation(self.HLO)
        # all-gather out 16*128*2 = 4096; all-reduce 8*16*4 = 512
        assert per["entry"] == 4096 + 512

    def test_fused_bytes_counts_dot_and_reduce_not_elementwise(self):
        got = fused_hbm_bytes(self.HLO)
        # dot: out 8*128*4 + in (8*16*4 + 16*128*2) = 4096+512+4096 = 8704
        # reduce out: 8*4 = 32 ; exponential excluded
        assert got == 8704 + 32

    def test_extrapolate(self):
        c1 = CellCosts(10.0, 100.0, 1.0, 7.0, 50.0)
        c2 = CellCosts(14.0, 130.0, 1.5, 7.0, 60.0)
        tot = extrapolate(c1, c2, 11)
        assert tot.flops == 10 + 10 * 4
        assert tot.fused_bytes == 50 + 10 * 10


@pytest.mark.slow
def test_mini_dryrun_on_8_fake_devices(tmp_path):
    """End-to-end SPMD proof at test scale: lower+compile smollm train on a
    (4,2) mesh with 8 fake host devices, in a subprocess (device count must
    be set before jax init)."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_smoke_config
        from repro.launch.steps import build_model, jit_train_step
        from repro.optim import AdamW

        mesh = jax.make_mesh(
            (4, 2), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )
        cfg = get_smoke_config("qwen3-moe-30b-a3b")  # exercises the EP island
        model = build_model(cfg, mesh, dtype=jnp.float32, remat="none")
        batch_sds = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        step, abstract, state_sh, batch_sh = jit_train_step(model, AdamW(), mesh, batch_sds)
        with jax.set_mesh(mesh):
            compiled = step.lower(abstract, batch_sds).compile()
            from repro.launch.roofline import first_cost_analysis
            ca = first_cost_analysis(compiled)
            assert ca.get("flops", 0) > 0
            # run it for real on the 8 fake devices
            import numpy as np
            params = model.init(jax.random.key(0))
            opt = AdamW()
            from repro.launch.steps import TrainState
            state = jax.device_put(TrainState(params, opt.init(params)), state_sh)
            batch = jax.device_put({"tokens": jnp.zeros((8, 16), jnp.int32)}, batch_sh)
            state, metrics = step(state, batch)
            assert np.isfinite(float(metrics["loss"]))
        print("MINI_DRYRUN_OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        timeout=600,
    )
    assert "MINI_DRYRUN_OK" in out.stdout, out.stderr[-3000:]
