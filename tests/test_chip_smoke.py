"""CPU rehearsal of `chip_smoke.py`: the script's own phase functions at toy
widths (Pallas kernels in interpret mode, virtual devices for the cross-chip
phase), and its refusal to pass without a TPU.

This is rehearsal 1 and 2 of the on-chip-measurement guide kept as tests:
it finds wrong paths, arguments, control flow, meshes and sharding rules
before a chip call is spent. It says nothing about the chip.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WIDTHS = dict(vocab_size=64, d_model=32, n_heads=2, d_ff=64, seq_len=64)
TRAIN = dict(n_layers=2, remat=True, batch=4, shards=2, batches_per_shard=2,
             passes=3, learning_rate=1e-2, kernel_shape=(1, 64, 2, 16))
SERVE = dict(batch_buckets=(1, 2), seq_buckets=(16, 32, 64), kv_blocks=32,
             kv_block_tokens=8, prompt_lens=(3, 9, 20, 40), max_new_tokens=4)
RESCALE = dict(n_layers=2, remat=True, batch=8, shards=32,
               batches_per_shard=1, leg_steps=8, learning_rate=1e-2)


def test_without_a_tpu_the_script_fails_at_the_device_phase():
    """`JAX_PLATFORMS=cpu python chip_smoke.py`: non-zero, the phase named,
    and no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "phase 'device' FAILED" in out.stderr
    assert "needs a TPU" in out.stderr


def test_a_failure_in_a_later_phase_is_named_and_not_carried_past(capsys):
    def boom():
        raise ValueError("forced")

    with pytest.raises(ValueError, match="forced"):
        chip_smoke.run_phase("serve", boom)
    assert "phase 'serve' FAILED" in capsys.readouterr().err


def test_train_then_serve_phases_pass_at_toy_widths(tmp_path, capsys):
    device = jax.devices()[:1]
    report, handoff = chip_smoke.run_phase(
        "train", chip_smoke.train_phase, WIDTHS, TRAIN, str(tmp_path), device)
    assert report["steps"] == report["state_step"] == 12
    assert report["last_loss"] < report["first_loss"]
    # on the CPU the step runs the kernel in the interpreter, and says so
    assert report["pallas_interpreter_in_step"] and not report["kernel_in_step"]
    assert max(report["kernel_vs_dense_rel_err"].values()) \
        <= chip_smoke.KERNEL_TOL

    served = chip_smoke.run_phase("serve", chip_smoke.serve_phase, SERVE,
                                  str(tmp_path), handoff)
    assert "params" not in handoff  # handed over, not kept alive twice
    assert served["requests"] == 4 and served["tokens_generated"] == 16
    assert served["tokens_checked"] == 16
    assert served["jit_cache_before"] == served["jit_cache_after"] == 0
    # every phase's report is one parseable line on stdout
    printed = {line.split(": ", 1)[0]: json.loads(line.split(": ", 1)[1])
               for line in capsys.readouterr().out.splitlines()
               if line.startswith(("train: ", "serve: "))}
    assert printed["train"]["steps"] == 12
    assert printed["serve"]["requests"] == 4


def test_rescale_phase_passes_on_four_virtual_devices(tmp_path, monkeypatch):
    """4 -> 2 -> 4 through the real rescale path against the static run; the
    toy model's 512-token batches are noisier than the chip's 8,192."""
    monkeypatch.setattr(chip_smoke, "RESCALE_BAND", 0.2)
    report = chip_smoke.rescale_phase(WIDTHS, RESCALE, str(tmp_path),
                                      jax.devices()[:4])
    assert [(r["from_world"], r["to_world"]) for r in report["rescales"]] \
        == [(2, 1), (1, 2)]
    assert [leg["devices"] for leg in report["legs"]] \
        == [[0, 1, 2, 3], [0, 1], [0, 1, 2, 3]]
    assert report["restore_sources"] == ["init", "blob", "blob"]
    assert report["steps_elastic"] == report["steps_static"] == 32
    assert report["max_abs_loss_delta_before_rescale"] == 0.0


def test_reference_forward_agrees_with_the_models_own_prefill():
    """The plain float32 forward the serve phase checks tokens against is the
    same function as `make_prefill_step` (bf16 matmuls) up to rounding."""
    import numpy as np
    from edl_tpu.models import transformer
    from edl_tpu.parallel import MeshSpec, build_mesh

    model = transformer.make_model(**WIDTHS, n_layers=2)
    mesh = build_mesh(MeshSpec({"data": 1}), jax.devices()[:1])
    params = model.init(jax.random.PRNGKey(1), mesh)
    tokens = np.random.default_rng(1).integers(1, 64, size=16).astype(np.int32)
    logits = np.asarray(chip_smoke.reference_logits(model.config, params,
                                                    tokens))
    assert logits.shape == (16, 64) and np.isfinite(logits).all()
    nxt, _, _ = jax.jit(transformer.make_prefill_step(model.config))(
        params, tokens[None], np.array([16], np.int32))
    row = logits[-1]
    assert row.max() - row[int(nxt[0])] < chip_smoke.NEAR_TIE
