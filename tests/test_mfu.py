"""FLOPs/MFU accounting (edl_tpu.tools.mfu + models' analytic formulas)."""

import dataclasses
import types

import jax
import numpy as np
import pytest

from edl_tpu.models import ctr, fit_a_line, mnist, resnet, transformer, word2vec
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.tools.mfu import flops_per_step, mfu_fields, peak_tflops_per_chip


def test_every_zoo_model_declares_analytic_flops():
    for model in (ctr.MODEL, fit_a_line.MODEL, mnist.MODEL, resnet.MODEL,
                  word2vec.MODEL, transformer.MODEL):
        assert model.flops_per_step is not None, model.name
        f = model.flops_per_step(16)
        assert f > 0
        # linear in batch size by construction
        assert model.flops_per_step(32) == pytest.approx(2 * f)


def test_resnet50_matches_published_flops():
    # torchvision reports ~4.09 GMACs for ResNet-50 @ 224 => ~8.2 GFLOPs.
    fwd = resnet._flops_fwd_per_image(resnet.MODEL.config)
    assert 7.5e9 < fwd < 8.8e9


def test_transformer_flops_track_config():
    small = transformer.make_model(n_layers=2).flops_per_step(4)
    big = transformer.make_model(n_layers=4).flops_per_step(4)
    cfg = transformer.TransformerConfig()
    per_layer_fwd = (
        8 * cfg.d_model ** 2 + 4 * cfg.d_model * cfg.d_ff
        + 2 * cfg.seq_len * cfg.d_model
    )
    # adding 2 layers adds exactly their block FLOPs (head term constant)
    assert big - small == pytest.approx(3 * 2 * per_layer_fwd * cfg.seq_len * 4)


def test_peak_table():
    v4 = types.SimpleNamespace(device_kind="TPU v4", platform="tpu")
    assert peak_tflops_per_chip(v4) == 275.0
    v6 = types.SimpleNamespace(device_kind="TPU v6e", platform="tpu")
    assert peak_tflops_per_chip(v6) == 918.0
    # the strings jax actually reports for v5e / Trillium
    v5l = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert peak_tflops_per_chip(v5l) == 197.0
    v6l = types.SimpleNamespace(device_kind="TPU v6 lite", platform="tpu")
    assert peak_tflops_per_chip(v6l) == 918.0
    cpu = types.SimpleNamespace(device_kind="cpu", platform="cpu")
    assert peak_tflops_per_chip(cpu) is None


def test_unknown_accelerator_is_an_error_not_a_default():
    unknown = types.SimpleNamespace(device_kind="TPU v9x", platform="tpu")
    with pytest.raises(ValueError, match="v9x"):
        peak_tflops_per_chip(unknown)
    model = transformer.make_model(d_model=64, n_layers=1, n_heads=2, d_ff=64)
    with pytest.raises(ValueError, match="v9x"):
        mfu_fields(model, 8, steps_per_sec=1.0, device=unknown)


def test_mfu_fields_analytic():
    dev = types.SimpleNamespace(device_kind="TPU v4", platform="tpu")
    model = transformer.make_model(
        d_model=768, n_layers=12, n_heads=12, d_ff=3072, seq_len=1024
    )
    out = mfu_fields(model, 8, steps_per_sec=20.0, n_chips=1, device=dev)
    assert out["flops_method"] == "analytic"
    # 5.85e12 flops/step * 20 steps/s ~= 117 TF/s => ~42.5% of v4 peak
    assert out["tflops_per_sec"] == pytest.approx(116.9, rel=0.01)
    assert out["mfu"] == pytest.approx(0.425, abs=0.005)


def test_cost_analysis_fallback():
    mesh = build_mesh(MeshSpec({"data": 1}), jax.devices()[:1])
    bare = dataclasses.replace(fit_a_line.MODEL, flops_per_step=None)
    flops, method = flops_per_step(bare, 64, mesh)
    if flops is None:  # cost analysis availability varies by backend
        assert "unavailable" in method
    else:
        assert method == "xla_cost_analysis"
        # fwd+bwd of a (64, 13) linear regression: small but nonzero
        assert flops > 2 * 13 * 64


def test_mfu_fields_degrade_without_flops():
    bare = dataclasses.replace(fit_a_line.MODEL, flops_per_step=None)
    out = mfu_fields(bare, 64, steps_per_sec=10.0)
    assert out["model_flops"] is None
    assert out["mfu"] is None
