"""Launch-layer units: shape skips, unrolled configs, roofline math."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS, get_config
from repro.launch.roofline import (
    CellCosts,
    flash_io_bytes,
    model_flops,
    moe_cpu_excess,
    rwkv_inner_correction,
)
from repro.launch.specs import batch_specs_for, cell_is_runnable
from repro.launch.steps import OPT_LEVELS, build_model
from repro.models import SHAPES


def test_skip_policy_matches_design():
    skipped = {a for a in ARCHS if not cell_is_runnable(a, "long_500k")[0]}
    assert skipped == {
        "smollm-135m",
        "starcoder2-15b",
        "phi4-mini-3.8b",
        "qwen3-moe-30b-a3b",
        "deepseek-v3-671b",
        "seamless-m4t-medium",
        "qwen2-vl-2b",
    }
    for a in ARCHS:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert cell_is_runnable(a, s)[0]


def test_total_cell_count_is_40():
    cells = [(a, s) for a in ARCHS for s in SHAPES]
    assert len(cells) == 40
    runnable = [c for c in cells if cell_is_runnable(*c)[0]]
    assert len(runnable) == 33


@pytest.mark.parametrize("arch", ARCHS)
def test_batch_specs_cover_all_shapes(arch):
    cfg = get_config(arch)
    for s in SHAPES.values():
        specs = batch_specs_for(cfg, s)
        if s.kind == "decode":
            assert set(specs) == {"token", "pos"}
            assert specs["token"].shape == (s.global_batch,)
        else:
            assert specs["tokens"].shape == (s.global_batch, s.seq_len)
            if cfg.family == "vlm":
                assert specs["positions"].shape[0] == 3
            if cfg.family == "audio":
                assert specs["enc_embeds"].shape == (
                    s.global_batch, cfg.encoder_seq, cfg.d_model
                )


def test_unrolled_cfg_layer_count():
    from repro.launch.dryrun import _unrolled_cfg

    cfg = get_config("gemma3-27b")
    u1 = _unrolled_cfg(cfg, 1)
    assert u1.n_layers == len(cfg.prefix) + len(cfg.period) + len(cfg.suffix)
    assert u1.n_periods == 0
    u2 = _unrolled_cfg(cfg, 2)
    assert u2.n_layers - u1.n_layers == len(cfg.period)


def test_model_flops_semantics():
    cfg = get_config("smollm-135m")
    tr = model_flops(cfg, SHAPES["train_4k"], 1e8, 1e8)
    pf = model_flops(cfg, SHAPES["prefill_32k"], 1e8, 1e8)
    de = model_flops(cfg, SHAPES["decode_32k"], 1e8, 1e8)
    assert tr == 6 * 1e8 * 256 * 4096
    assert pf == 2 * 1e8 * 32 * 32768
    assert de == 2 * 1e8 * 128


def test_moe_excess_zero_for_dense():
    cfg = get_config("smollm-135m")
    assert moe_cpu_excess(cfg, SHAPES["train_4k"], {"data": 16, "model": 16}) == 0.0
    moe = get_config("qwen3-moe-30b-a3b")
    assert moe_cpu_excess(moe, SHAPES["train_4k"], {"data": 16, "model": 16}) > 0


def test_rwkv_correction_only_for_rwkv():
    assert rwkv_inner_correction(get_config("smollm-135m"), SHAPES["train_4k"], 256) == 0
    assert rwkv_inner_correction(get_config("rwkv6-1.6b"), SHAPES["train_4k"], 256) > 0


def test_flash_io_scales_with_arch():
    sm = flash_io_bytes(get_config("smollm-135m"), SHAPES["prefill_32k"], {"data": 16, "model": 16})
    g3 = flash_io_bytes(get_config("gemma3-27b"), SHAPES["prefill_32k"], {"data": 16, "model": 16})
    assert 0 < sm < g3
    assert flash_io_bytes(get_config("rwkv6-1.6b"), SHAPES["prefill_32k"], {"data": 16, "model": 16}) == 0


def test_opt_levels_monotone_features():
    assert set(OPT_LEVELS) == {"O0", "O1", "O2", "O3", "O4"}
    assert OPT_LEVELS["O0"] == {}
    assert OPT_LEVELS["O4"]["cache_update"] == "dus"


def test_build_model_pin_wiring():
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((16, 16), ("data", "model"))
    m = build_model(get_config("smollm-135m"), mesh, opt="O2")
    assert m.pin_axes == ("data",)
    m0 = build_model(get_config("smollm-135m"), mesh, opt="O0")
    assert m0.pin_mesh is None
