"""What PR 27 added to the benchmark for the hybrid family, on the CPU:
`benchmarks/costs_hybrid.py` against hand-computed figures for one layer of
each kind, the new `BENCHMARK.json`, the configuration file against the
catalog's rules, the readers `mfu_hybrid`, `trace_scope_time_share` and
`scope_roofline` on a hand-built trace, and runner `train_model` end to end
at toy widths. No chip, no timing."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

import costs_hybrid  # noqa: E402
import run  # noqa: E402
import trace_scopes as ts  # noqa: E402
from cell import ReadContext  # noqa: E402

CELL = "train_nemotron3nano_1chip"
US = 1_000_000  # picoseconds in a microsecond
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return run.load_json(BENCH, "configs", "nemotron-3-nano-30b-a3b.json")


def sizes():
    return dict(run.model_kwargs(config()), seq_len=8192)


# -- costs, by hand --------------------------------------------------------------


def test_forward_flops_of_one_layer_of_each_kind_by_hand():
    per = costs_hybrid.forward_flops_per_token(**sizes())
    # M: in_proj 2688 x 10304, conv 4 taps x 6144, out_proj 4096 x 2688, and
    # the scan: in a chunk of 128, C B^T (128 x 128 a group of 8) and its
    # product with x (128 x 64 a head of 64), both halved by the mask; the
    # chunk's state and the carried state's output, 64 x 128 a head each
    ssd = 0.5 * (2 * 128 * 128 * 8 + 2 * 128 * 64 * 64) \
        + 2 * (2 * 64 * 128 * 64)
    assert ssd == 2_752_512
    assert per["M"] == 2 * 2688 * 10304 + 2 * 4 * 6144 + ssd \
        + 2 * 4096 * 2688 == 80_216_064
    # *: q 4096, k and v 256 each, o 4096; scores and values at S 8,192 over
    # the 4096 of the query heads, halved
    assert per["*"] == 2 * 2688 * (4096 + 512) + 2 * 4096 * 2688 \
        + 0.5 * 4 * 8192 * 4096 == 113_901_568
    # E: router 128, shared expert 3712, and 6 x 8 / 128 of a token through
    # an expert of 1856
    assert per["E"] == 2 * 2688 * 128 + 4 * 2688 * 3712 \
        + 6 * 8 / 128 * 4 * 2688 * 1856 == 48_082_944
    assert per["head"] == 2 * 2688 * 16384
    assert per["-"] == 4 * 2688 * 1856
    total = 3 * (4 * per["M"] + 4 * per["E"] + per["*"] + per["head"])
    assert costs_hybrid.train_flops_per_token(**sizes()) == total
    assert total == pytest.approx(2.1455e9, rel=1e-4)
    # the program's own count (Model.flops_per_step) is the same work
    from edl_tpu.models import resolve

    model = resolve("hybrid", sizes())
    assert model.flops_per_step(2) == pytest.approx(total * 2 * 8192)


def test_kernel_floors_by_hand():
    tokens = 2 * 8192
    kw = sizes()
    # the SSD core is bound by memory: x and y 4096 wide, B and C 1024, dt 64
    ssd_bytes = (2 * 4096 + 2 * 1024 + 64) * 2
    assert costs_hybrid.ssd_forward_bytes_per_token_layer(**kw) == ssd_bytes
    assert costs_hybrid.ssd_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(3 * 4 * tokens * ssd_bytes / 819e9)
    fast_hbm = dict(PEAKS, hbm_bytes_per_s=1e15)
    assert costs_hybrid.ssd_floor_seconds(tokens, fast_hbm, **kw) \
        == pytest.approx(3 * 4 * tokens * 2_752_512 / 197e12)
    # grouped-query attention's core is bound by compute; k and v are read
    # at the K/V heads' 256, not the query heads' 4096
    assert costs_hybrid.attn_forward_bytes_per_token_layer(**kw) \
        == (2 * 4096 + 2 * 256) * 2
    assert costs_hybrid.attn_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(3 * tokens * 0.5 * 4 * 8192 * 4096 / 197e12)
    # the held experts: 6,144 assignments a step a layer
    held = tokens * 6 * 8 / 128
    assert held == 6144
    flops = 3 * 4 * held * 4 * 2688 * 1856
    assert costs_hybrid.experts_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(flops / 197e12)
    weights = 8 * 2 * 2688 * 1856 * 2
    rows = held * 2 * (2688 + 1856) * 2
    slow_hbm = dict(PEAKS, hbm_bytes_per_s=1e9)
    assert costs_hybrid.experts_floor_seconds(tokens, slow_hbm, **kw) \
        == pytest.approx(4 * (4 * weights + 3 * rows) / 1e9)
    # and for the assignments a run counted: at a third of uniform routing's
    # the matrices' bytes bound it, not the operations
    assert costs_hybrid.experts_floor_seconds(
        tokens, PEAKS, held_per_token=0.125, **kw) == pytest.approx(
            4 * (4 * weights + 3 * rows / 3) / 819e9)
    assert 4 * (4 * weights + rows) / 819e9 > flops / 3 / 197e12


# -- the contract and the configuration file -------------------------------------------


def test_benchmark_json_is_valid_and_the_cell_is_there():
    bench = run.load_json(REPO, "BENCHMARK.json")
    run.validate(bench, BENCH)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b", "fixed_b2_s8192", 1)
    assert "768" in cell["why"] and "12,288" in cell["why"]
    # PR 32 added the sparse cell, PR 35 the sliding-window cell
    assert len(bench["workloads"]) == 4
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = run.load_json(REPO, entry["file"])
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    for e in bench["configs"] + bench["workloads"]:
        assert len(e["why"]) <= 200 and "\n" not in e["why"]
    reports = [m["name"] for m in bench["per_layer"] if run.reports(m, CELL)]
    assert len(reports) == 24 and "mfu_pct.train" not in reports \
        and "attn_roofline_pct.train" not in reports
    # PR 28's counter that the SSD kernels ran: data on a reader that was there
    assert reports[-2] == "ssd_kernel_time_pct.train"
    how = run.load_json(BENCH, "layer_metrics", "ssd_kernel_time_pct.train.json")
    assert how["reader"] == "trace_scope_time_share"
    assert how["args"] == {"scopes": ["ssd_fwd", "ssd_bwd"]}
    # PR 30's: all of attn_core, in both cells; less the kernels' share it is
    # what XLA puts between the block's arrays and the kernels' operands
    assert reports[-1] == "attn_core_time_pct.train"
    dense = next(w["name"] for w in bench["workloads"] if w["name"] != CELL)
    core = next(m for m in bench["per_layer"]
                if m["name"] == "attn_core_time_pct.train")
    assert run.reports(core, dense)
    how = run.load_json(BENCH, "layer_metrics", "attn_core_time_pct.train.json")
    assert how["reader"] == "trace_scope_time_share"
    assert how["args"] == {"scopes": ["attn_core"]}
    # every metric that was there still lists the cell it listed
    for m in bench["per_layer"]:
        listed = [w for w in m["workloads"] if w not in (
            "train_keyevl2_1chip", "train_smallthinker21b_1chip")]
        if not m["name"].endswith(("train_hybrid",)) \
                and listed not in ([CELL], []):
            assert listed[0] == "train_gpt2m_1chip"


def test_the_configuration_keeps_every_published_number():
    """The catalog's rule: every number of the published config under its
    own key, unchanged unless the key is in ``reduced``; ``reduced`` names
    no width."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    assert cfg["hybrid_override_pattern"] \
        == row["config"]["hybrid_override_pattern"][:9]
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]
    assert cfg["router_width"] == row["config"]["n_routed_experts"]
    assert all(cfg[k] for k in ("deployment", "assumed", "departures"))


# -- the readers on a hand-built trace ---------------------------------------------------

FWD = "jit(_step)/fwd_bwd/jvp()/"
BWD = "jit(_step)/fwd_bwd/transpose(jvp())/checkpoint/"
#: (instruction, op_name, start us, duration us): one traced step
OPS = [
    ("fusion.1", FWD + "embed/gather", 0, 10),
    ("fusion.2", FWD + "mamba_mixer/mamba_proj/dot_general", 10, 40),
    ("fusion.3", FWD + "mamba_mixer/ssd_core/dot_general", 50, 30),
    ("fusion.4", FWD + "moe/moe_route/top_k", 80, 5),
    ("fusion.5", FWD + "moe/moe_dispatch/sort", 85, 5),
    # XLA names the grouped product's kernels itself and drops the scopes
    ("ragged-dot-none.1[tpu_custom_call]", "ragged-dot-none", 90, 20),
    ("fusion.6", FWD + "moe/moe_shared/dot_general", 110, 20),
    ("fusion.7", FWD + "moe/moe_combine/gather", 130, 10),
    ("flash_fwd.3[tpu_custom_call]", FWD + "attn/attn_core/flash_fwd/pallas_call", 140, 20),
    ("fusion.8", BWD + "rematted_computation/mamba_mixer/ssd_core/dot_general", 160, 30),
    ("fusion.9", BWD + "mamba_mixer/ssd_core/transpose", 190, 60),
    ("ragged-dot-metadata.2[tpu_custom_call]", "ragged-dot-metadata", 250, 2),
    ("ragged-dot-none.9[tpu_custom_call]", "ragged-dot-none", 252, 30),
    ("fusion.10", BWD + "moe/moe_experts/mul", 282, 8),
    ("fusion.11", "jit(_step)/optimizer/add", 300, 10),
]


@pytest.fixture
def readers(monkeypatch):
    trace = ts.ScopedTrace(
        devices={"/device:TPU:0": [ts.Op(n, o, s * US, d * US)
                                   for n, o, s, d in OPS]},
        modules={"/device:TPU:0": [("jit__step(1)", 0, 310 * US)]})
    monkeypatch.setattr(ts, "current", lambda: trace)

    def read(metric, ctx):
        how = run.load_json(BENCH, "layer_metrics", f"{metric}.json")
        reader = run.load_module(BENCH, "readers", how["reader"])
        return reader.read(ctx, **how.get("args", {}))

    return read


def context(**values):
    return ReadContext(spans=[], values=values, trace=None,
                       device={"kind": "TPU v5 lite"}, chips=1,
                       model_kwargs=run.model_kwargs(config()))


STEP = dict(steady_tokens_per_s=16384 / 0.5, step_s_p50=0.5, seq_len=8192.0)


def test_time_shares_by_scope(readers):
    busy = 300.0  # the sum of the durations: nothing nests
    assert readers("mamba_time_pct.train", context()) \
        == pytest.approx(100 * (40 + 30 + 30 + 60) / busy)
    assert readers("moe_time_pct.train", context()) \
        == pytest.approx(100 * (5 + 5 + 20 + 20 + 10 + 40) / busy)
    assert readers("moe_route_time_pct.train", context()) \
        == pytest.approx(100 * (5 + 5 + 10) / busy)


def test_rooflines_are_the_floor_over_the_seconds_under_the_scope(readers):
    kw, tokens = sizes(), 16384
    assert readers("ssd_roofline_pct.train", context(**STEP)) == pytest.approx(
        100 * costs_hybrid.ssd_floor_seconds(tokens, PEAKS, **kw) / 120e-6)
    assert readers("moe_experts_roofline_pct.train", context(**STEP)) \
        == pytest.approx(100 * costs_hybrid.experts_floor_seconds(
            tokens, PEAKS, **kw) / 60e-6)
    assert readers("attn_roofline_pct.train_hybrid", context(**STEP)) \
        == pytest.approx(100 * costs_hybrid.attn_floor_seconds(
            tokens, PEAKS, **kw) / 20e-6)
    # the held experts' floor follows the assignments the runner counted
    assert readers("moe_experts_roofline_pct.train",
                   context(moe_held_per_token=0.25, **STEP)) \
        == pytest.approx(100 * costs_hybrid.experts_floor_seconds(
            tokens, PEAKS, held_per_token=0.25, **kw) / 60e-6)
    # the flash kernels by their names: the synthetic step has one, 20 us
    assert readers("flash_time_pct.train_hybrid", context()) \
        == pytest.approx(100 * 20 / 300)


def test_first_step_distances_read_one_for_a_state_left_unchanged():
    """The comparison that holds the timed step to the reference, on small
    trees: a sound step reads near 0 three times; an unchanged state reads 1
    for the update; a wrong rate shows in the update and the optimizer; a
    leaf whose gradient is left out reads 1 on that leaf."""
    import numpy as np

    import reference_hybrid as ref

    rng = np.random.default_rng(0)
    grads = {"a": rng.normal(size=(64, 8)).astype(np.float32),
             "b": rng.normal(size=(32,)).astype(np.float32),
             "bias": np.zeros((4,), np.float32)}
    before = {k: rng.normal(size=v.shape).astype(np.float32)
              for k, v in grads.items()}
    lr = 3e-4

    def run_step(g, rate=lr):
        after = {k: before[k] + ref.adam_first_step(g[k], rate) for k in g}
        moment = {k: (1 - ref.ADAM_B1) * g[k] for k in g}
        return ref.first_step_distances(before, after, moment, grads, lr)

    sound = run_step(grads)
    assert all(far < 1e-3 for far, _ in sound.values())
    assert sound["gradient"][1]["['bias']"] == 0.0
    still = ref.first_step_distances(
        before, before, {k: 0.1 * g for k, g in grads.items()}, grads, lr)
    assert still["update"][0] == pytest.approx(1.0)
    assert still["optimizer"][0] == pytest.approx(1.0)
    assert still["gradient"][0] < 1e-6
    doubled = run_step(grads, rate=2 * lr)
    assert doubled["update"][0] == pytest.approx(1.0, abs=1e-3)
    assert doubled["optimizer"][0] > ref.OPTIMIZER_TOL
    lost = run_step(dict(grads, b=np.zeros_like(grads["b"])))
    assert lost["gradient"][1]["['b']"] == pytest.approx(1.0)
    assert lost["gradient"][1]["['a']"] < 1e-6
    assert lost["optimizer"][0] < 1e-3  # Adam of its own gradient, still
    # a sign flipped in one element of a hundred reads 2 sqrt(1 / 100)
    flipped = {k: g.copy() for k, g in grads.items()}
    flipped["a"].reshape(-1)[::100] *= -1
    share = (flipped["a"] != grads["a"]).mean()
    assert run_step(flipped)["update"][1]["['a']"] == pytest.approx(
        2 * share ** 0.5, rel=1e-3)


def test_mfu_and_the_counter_metric(readers):
    flops = costs_hybrid.train_flops_per_token(**sizes())
    assert readers("mfu_pct.train_hybrid", context(**STEP)) \
        == pytest.approx(100 * 32768 * flops / 197e12)
    assert readers("moe_load_max_over_mean.train",
                   context(moe_load_max_over_mean=1.25)) == 1.25


def test_readers_find_nothing_where_there_is_nothing(readers, monkeypatch):
    for metric in ("mfu_pct.train_hybrid", "ssd_roofline_pct.train",
                   "moe_load_max_over_mean.train"):
        assert readers(metric, context()) is None  # the runner gave no values
    monkeypatch.setattr(ts, "current", lambda: ts.ScopedTrace(
        devices={"/device:TPU:0": [ts.Op("fusion.1", "jit(_step)/mlp", 0, US)]},
        modules={"/device:TPU:0": [("jit__step(1)", 0, US)]}))
    for metric in ("mamba_time_pct.train", "moe_time_pct.train",
                   "ssd_roofline_pct.train", "attn_roofline_pct.train_hybrid"):
        assert readers(metric, context(**STEP)) is None  # no such scope


# -- the runner, end to end at toy widths -------------------------------------------------


@pytest.mark.parametrize("change, failing", [
    (None, ()),
    ("all_to_held", ()),
    ("float8", ("grads_are_reference", "update_is_reference")),
    ("no_routed_experts", ("grads_are_reference",)),
])
def test_runner_train_model_end_to_end_at_toy_widths(tmp_path, monkeypatch,
                                                     change, failing):
    """Runner ``train_model`` through `run.run_cell` on a copy of
    ``benchmarks/`` with a toy hybrid configuration and cell dropped in as
    new files: the model by `resolve`, the reference by the name the
    configuration gives, every check of the real cell but the kernel's.
    Then the controls of ``control_hybrid.py`` through the same comparison:
    a fault comes out ``correct: false`` by the checks named and no other,
    total imbalance (several tiles of `_experts_held`) ``correct: true``."""
    import jax

    bdir = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, bdir,
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))

    def put(path, obj):
        with open(os.path.join(bdir, path), "w") as f:
            json.dump(obj, f)

    real = config()
    toy = dict(real, vocab_size=256, hidden_size=64,
               hybrid_override_pattern="ME*", mamba_num_heads=4,
               mamba_head_dim=16, n_groups=2, ssm_state_size=16, chunk_size=8,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               router_width=16, n_routed_experts=4, experts_first=4,
               num_experts_per_tok=2, moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=64, intermediate_size=32)
    put("configs/toy_hybrid.json", toy)
    put("traffic/toy_b4_s32.json", dict(
        run.load_json(bdir, "traffic", "fixed_b2_s8192.json"),
        batch=4, seq_len=32))
    put("workloads/toy_hybrid_train.json",
        run.load_json(bdir, "workloads", f"{CELL}.json"))
    bench = run.load_json(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "toy_hybrid", "source": "none",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy_hybrid.json"})
    bench["workloads"].append({"name": "toy_hybrid_train", "chips": 1,
                               "config": "toy_hybrid", "why": "toy",
                               "traffic": "toy_b4_s32"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy_hybrid_train")
    # the limit is sized for the cell's 16,384 tokens a step; 128 tokens at
    # toy widths average the bf16 rounding of far fewer logits
    import reference_hybrid

    monkeypatch.setattr(reference_hybrid, "LOSS_TOL", 5e-3)
    monkeypatch.setattr(reference_hybrid, "GRAD_TOL", 0.2)
    from edl_tpu.models import hybrid

    import control_hybrid

    monkeypatch.setattr(hybrid, "_ROW_TILE", 64)
    for name, replacement in control_hybrid.changes().get(change, {}).items():
        monkeypatch.setattr(hybrid, name, replacement)
    lines = []
    monkeypatch.setattr(run, "log", lines.append)
    run.validate(bench, bdir)
    out = run.run_cell(bench, bdir, "toy_hybrid_train", 2**31 + 77, 1.5,
                       False, jax.devices()[:1])
    checks = next(line for line in lines if line.startswith("checks: "))
    for name in ("losses_finite", "no_compile_in_window", "no_rescale",
                 "loss_towards_log_vocab", "no_token_dropped",
                 "assignments_conserved", "first_loss_is_reference",
                 "grads_are_reference", "update_is_reference",
                 "optimizer_is_adam"):
        assert f"'{name}': {name not in failing}" in checks, (checks, lines)
    assert out["correct"] == (not failing)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert any(line.startswith("routing, step") and "'dropped': 0" in line
               for line in lines)
    if change == "all_to_held":  # 256 assignments, all held: tiles of 64
        assert any(line.startswith("routing, step") and "'held': 256" in line
                   for line in lines)
