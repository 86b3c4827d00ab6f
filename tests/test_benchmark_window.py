"""What PR 35 added to the benchmark for the sliding-window family, on the
CPU: `benchmarks/costs_window.py` against hand-computed figures, the new
`BENCHMARK.json` entries, the configuration file against the catalog's
rules, the readers `mfu_window` and `scope_roofline_window` on a hand-built
trace, the reference's distances where a leaf takes no gradient, and runner
`train_window` with the three controls of `control_window.py` end to end at
toy widths. No chip, no timing."""

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

import costs_window  # noqa: E402
import run  # noqa: E402
import trace_scopes as ts  # noqa: E402
from cell import ReadContext  # noqa: E402

CELL = "train_smallthinker21b_1chip"
CONFIG = "smallthinker-21b-a3b-instruct"
TRAFFIC = "fixed_b1_s16384_ids37984"
US = 1_000_000  # picoseconds in a microsecond
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return run.load_json(BENCH, "configs", f"{CONFIG}.json")


def sizes():
    return dict(run.model_kwargs(config()), seq_len=16384)


# -- costs, by hand --------------------------------------------------------------


def test_forward_flops_of_each_layer_kind_by_hand():
    per = costs_window.forward_flops_per_token(**sizes())
    # a query sees t + 1 keys up to 4,096 and 4,096 after
    seen = (4096 * 4097 / 2 + (16384 - 4096) * 4096) / 16384
    assert costs_window.seen_keys_mean(16384, 4096) == seen == 3584.125
    assert costs_window.seen_keys_mean(16384) == 8192.5
    assert costs_window.seen_keys_mean(1024, 4096) == 512.5
    # q 3584, k and v 512 each, o 3584 on a hidden 2560
    proj = 2 * 2560 * (3584 + 1024) + 2 * 3584 * 2560
    assert per["W"] == proj + 4 * seen * 3584 == 93_325_056
    assert per["*"] == proj + 4 * 8192.5 * 3584 == 159_390_720
    # E: router 64, and 6 x 16 / 64 of a token through gate, up and down
    assert per["E"] == 2 * 2560 * 64 + 1.5 * 6 * 2560 * 768 == 18_022_400
    assert per["head"] == 2 * 2560 * 37984
    total = 3 * (per["*"] + 3 * per["W"] + 4 * per["E"] + per["head"])
    assert costs_window.train_flops_per_token(**sizes()) == total
    assert total == pytest.approx(2.1178e9, rel=1e-4)
    # the program's own count (Model.flops_per_step) is the same work but
    # for the causal half, which it takes as S / 2 where the costs count
    # (S + 1) / 2 keys a query
    from edl_tpu.models import resolve

    model = resolve("hybrid", sizes())
    assert model.flops_per_step(1) == pytest.approx(total * 16384, rel=1e-4)


def test_kernel_floors_by_hand():
    tokens, kw = 16384, sizes()
    # both attention floors are bound by compute
    assert costs_window.window_attn_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(3 * 3 * tokens * 4 * 3584.125 * 3584 / 197e12)
    assert costs_window.full_attn_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(3 * 1 * tokens * 4 * 8192.5 * 3584 / 197e12)
    assert costs_window.attn_forward_bytes_per_token_layer(**kw) \
        == (2 * 3584 + 2 * 512) * 2
    slow = dict(PEAKS, hbm_bytes_per_s=1e6)
    assert costs_window.window_attn_floor_seconds(tokens, slow, **kw) \
        == pytest.approx(3 * 3 * tokens * (2 * 3584 + 2 * 512) * 2 / 1e6)
    # the window's pairs over the causal pairs, a layer: 0.4375
    assert (costs_window.window_attn_floor_seconds(tokens, PEAKS, **kw) / 3) \
        / costs_window.full_attn_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(58_722_304 / 134_225_920)
    # the held experts: 24,576 assignments a step a layer, three matrices
    assert costs_window.held_assignments_per_token(**kw) == 1.5
    flops = 3 * 4 * tokens * 1.5 * 6 * 2560 * 768
    assert costs_window.experts_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(flops / 197e12)
    weights = 16 * 3 * 2560 * 768 * 2
    rows = 1.5 * tokens * 2 * (2560 + 768) * 2
    assert costs_window.experts_floor_seconds(tokens, slow, **kw) \
        == pytest.approx(4 * (4 * weights + 3 * rows) / 1e6)
    assert costs_window.experts_floor_seconds(
        tokens, PEAKS, held_per_token=0.5, **kw) \
        == pytest.approx(max(flops / 3 / 197e12,
                             4 * (4 * weights + rows) / 819e9))


# -- the contract and the configuration file -------------------------------------------

NEW = ("mfu_pct.train_window", "window_attn_roofline_pct.train",
       "full_attn_roofline_pct.train", "attn_window_time_pct.train",
       "attn_full_time_pct.train", "moe_experts_roofline_pct.train_reglu",
       "window_pairs_share.train")
JOINED = ("step_ms_p50.train", "device_idle_pct.train",
          "idle_attributed_pct.train", "fwd_time_pct.train",
          "recompute_time_pct.train", "bwd_time_pct.train",
          "optimizer_time_pct.train", "host_step_ms_p50.train",
          "input_wait_ms_p50.train", "lease_rpc_ms_p50.train",
          "flash_fwd_call_ms_p50.train", "flash_dq_call_ms_p50.train",
          "flash_dkv_call_ms_p50.train", "attn_core_time_pct.train",
          "flash_time_pct.train_hybrid", "moe_time_pct.train",
          "moe_route_time_pct.train", "moe_load_max_over_mean.train")


def test_benchmark_json_is_valid_and_the_cell_is_there():
    bench = run.load_json(REPO, "BENCHMARK.json")
    run.validate(bench, BENCH)
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, TRAFFIC, 1)
    assert "1,536" in cell["why"] and "6,144" in cell["why"]
    entry = bench["configs"][-1]
    cfg = run.load_json(REPO, entry["file"])
    assert entry["name"] == cell["config"]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    for e in bench["configs"] + bench["workloads"]:
        assert len(e["why"]) <= 200 and "\n" not in e["why"]
    assert len(bench["configs"]) == 4 and len(bench["workloads"]) == 4
    reports = [m["name"] for m in bench["per_layer"] if run.reports(m, CELL)]
    assert len(reports) == len(JOINED) + len(NEW) == 25
    assert set(reports) == set(JOINED) | set(NEW)
    # the new metrics stand at the end, in order, and list this cell alone
    assert tuple(m["name"] for m in bench["per_layer"][-len(NEW):]) == NEW
    for m in bench["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # a metric the cell joined lists it last, after the cells it listed
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            assert m["workloads"][-1] == CELL and len(m["workloads"]) >= 3
    e2e = next(m for m in bench["end_to_end"]
               if m["name"] == "train_tokens_per_s")
    assert e2e["workloads"] == ["train_gpt2m_1chip",
                                "train_nemotron3nano_1chip",
                                "train_keyevl2_1chip", CELL]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    traffic = run.load_json(BENCH, "traffic", f"{TRAFFIC}.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["warmup_steps"],
            traffic["queue_ahead"], traffic["batches_per_shard"]) \
        == (1, 16384, 3, 6, 1)
    workload = run.load_json(BENCH, "workloads", f"{CELL}.json")
    assert (workload["runner"], workload["optimizer"], workload["remat"],
            workload["learning_rate"], workload["traced_steps"]) \
        == ("train_window", "adam", True, 3e-4, 3)


def test_the_configuration_keeps_every_published_number():
    """The catalog's rule: every number of the published config under its
    own key, unchanged unless the key is in ``reduced``; nested groups whole;
    ``reduced`` names no width."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    assert all(k in cfg for k in row["config"])
    changed = {k for k, v in row["config"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "rope_layout", "sliding_window_layout"}
    assert set(cfg["published"]) == changed
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["moe_num_primary_experts"],
            cfg["published"]["vocab_size"]) == (52, 64, 151936)
    # the two layouts shorten with the depth: one whole period of them
    for key in ("rope_layout", "sliding_window_layout"):
        assert row["config"][key] == cfg[key] * 13 and cfg[key] == [0, 1, 1, 1]
    # the pattern spells the layouts: a layer with positions and a window is
    # W, one without is *, and an E follows each
    assert cfg["layer_pattern"] == "".join(
        ("W" if windowed else "*") + "E"
        for windowed in cfg["sliding_window_layout"])
    assert cfg["rope_layout"] == cfg["sliding_window_layout"]
    assert cfg["router_width"] == row["config"]["moe_num_primary_experts"]
    assert cfg["vocab_size"] * 4 == row["config"]["vocab_size"]
    kw = run.model_kwargs(cfg)
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["expert_width"], kw["top_k"], kw["rope_theta"], kw["window"],
            kw["norm_eps"], kw["expert_act"], kw["router_score"],
            kw["shared_width"], kw["experts_count"], kw["n_experts"],
            kw["vocab_size"], kw["router_input"], kw["router_frozen"]) == (
        2560, 28, 4, 128, 768, 6, 1.5e6, 4096, 1e-6, "relu", "softmax", 0,
        16, 64, 37984, "previous", True)
    for key in ("router_input", "window", "rotary", "router", "experts",
                "initialiser"):
        assert cfg["assumed"][key]
    assert all(cfg[k] for k in ("deployment", "cut", "departures"))
    assert "routers are frozen" in cfg["departures"][0]


def test_the_configuration_file_counts_its_parameters():
    import jax
    import numpy as np
    from edl_tpu.models import resolve
    from edl_tpu.parallel import MeshSpec, build_mesh

    cfg = config()
    model = resolve(cfg["model"], sizes())
    mesh = build_mesh(MeshSpec({"data": 1}), jax.devices()[:1])
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), mesh))
    count = lambda tree: sum(int(np.prod(leaf.shape))
                             for leaf in jax.tree_util.tree_leaves(tree))
    # attention 20,971,520 + the pre-norm 2,560, global and window alike
    assert count(shapes["layers"]["00*"]) == count(shapes["layers"]["02W"]) \
        == 20_971_520 + 2560
    assert sorted(shapes["layers"]["02W"]) == ["norm", "wk", "wo", "wq", "wv"]
    # router 163,840 + 16 experts of 3 x 2560 x 768 + the pre-norm
    assert count(shapes["layers"]["01E"]) == 163_840 + 16 * 5_898_240 + 2560
    assert count(shapes) == cfg["parameters"] == 656_529_920
    assert sorted(shapes["layers"]["01E"]) == ["norm", "router", "w_down",
                                               "w_up"]


# -- the readers on a hand-built trace ---------------------------------------------------

FWD = "jit(_step)/fwd_bwd/jvp()/"
BWD = "jit(_step)/fwd_bwd/transpose(jvp())/checkpoint/"
#: (instruction, op_name, start us, duration us): one traced step
OPS = [
    ("fusion.1", FWD + "embed/gather", 0, 10),
    ("fusion.2", FWD + "attn/attn_proj/dot_general", 10, 10),
    ("flash_fwd.3[tpu_custom_call]",
     FWD + "attn/attn_core/attn_full/flash_fwd/pallas_call", 20, 40),
    ("fusion.3", FWD + "moe/moe_route/top_k", 60, 5),
    ("ragged-dot-none.1[tpu_custom_call]", "ragged-dot-none", 65, 20),
    ("fusion.4", FWD + "moe/moe_experts/mul", 85, 5),
    ("flash_fwd.4[tpu_custom_call]",
     FWD + "attn/attn_core/attn_window/flash_fwd/pallas_call", 90, 20),
    ("flash_bwd_dq.5[tpu_custom_call]",
     BWD + "attn/attn_core/attn_window/flash_bwd_dq/pallas_call", 110, 30),
    ("fusion.5", BWD + "attn/attn_core/attn_window/reduce_sum", 140, 10),
    ("flash_bwd_dkv.6[tpu_custom_call]",
     BWD + "attn/attn_core/attn_full/flash_bwd_dkv/pallas_call", 150, 50),
    ("fusion.8", "jit(_step)/optimizer/add", 200, 20),
]


@pytest.fixture
def readers(monkeypatch):
    trace = ts.ScopedTrace(
        devices={"/device:TPU:0": [ts.Op(n, o, s * US, d * US)
                                   for n, o, s, d in OPS]},
        modules={"/device:TPU:0": [("jit__step(1)", 0, 220 * US)]})
    monkeypatch.setattr(ts, "current", lambda: trace)

    def read(metric, ctx):
        how = run.load_json(BENCH, "layer_metrics", f"{metric}.json")
        reader = run.load_module(BENCH, "readers", how["reader"])
        return reader.read(ctx, **how.get("args", {}))

    return read


def context(kwargs=None, **values):
    return ReadContext(spans=[], values=values, trace=None,
                       device={"kind": "TPU v5 lite"}, chips=1,
                       model_kwargs=run.model_kwargs(config())
                       if kwargs is None else kwargs)


STEP = dict(steady_tokens_per_s=16384 / 1.0, step_s_p50=1.0, seq_len=16384.0)


def test_new_metrics_on_a_hand_built_trace(readers):
    kw, tokens, busy = sizes(), 16384, 220.0
    assert readers("window_attn_roofline_pct.train", context(**STEP)) \
        == pytest.approx(100 * costs_window.window_attn_floor_seconds(
            tokens, PEAKS, **kw) / 60e-6)
    assert readers("full_attn_roofline_pct.train", context(**STEP)) \
        == pytest.approx(100 * costs_window.full_attn_floor_seconds(
            tokens, PEAKS, **kw) / 90e-6)
    assert readers("moe_experts_roofline_pct.train_reglu",
                   context(moe_held_per_token=1.25, **STEP)) \
        == pytest.approx(100 * costs_window.experts_floor_seconds(
            tokens, PEAKS, held_per_token=1.25, **kw) / 25e-6)
    assert readers("attn_window_time_pct.train", context()) \
        == pytest.approx(100 * 60 / busy)
    assert readers("attn_full_time_pct.train", context()) \
        == pytest.approx(100 * 90 / busy)
    assert readers("mfu_pct.train_window", context(**STEP)) \
        == pytest.approx(100 * 16384 * costs_window.train_flops_per_token(
            **kw) / 197e12)
    assert readers("window_pairs_share.train",
                   context(window_pairs_share=0.4375)) == 0.4375
    # the metrics the cell joined read the same names here: both kinds of
    # core are under attn_core, and the kernels are found by name
    assert readers("attn_core_time_pct.train", context()) \
        == pytest.approx(100 * 150 / busy)
    assert readers("flash_time_pct.train_hybrid", context()) \
        == pytest.approx(100 * 140 / busy)
    assert readers("moe_time_pct.train", context()) \
        == pytest.approx(100 * 30 / busy)
    assert readers("flash_fwd_call_ms_p50.train", context()) \
        == pytest.approx(0.03)


def test_readers_find_nothing_where_there_is_nothing(readers, monkeypatch):
    for metric in ("mfu_pct.train_window", "window_attn_roofline_pct.train",
                   "window_pairs_share.train"):
        assert readers(metric, context()) is None  # the runner gave no values
    # a configuration without a window (the accepted cells): nothing
    sparse = run.model_kwargs(run.load_json(
        BENCH, "configs", "keye-vl-2.0-30b-a3b.json"))
    for metric in ("window_attn_roofline_pct.train", "mfu_pct.train_window"):
        assert readers(metric, context(sparse, **STEP)) is None
    # a program that does not name the scopes (the parent's): nothing
    monkeypatch.setattr(ts, "current", lambda: ts.ScopedTrace(
        devices={"/device:TPU:0": [ts.Op(
            "flash_fwd.1", "jit(_step)/attn/attn_core/flash_fwd", 0, US)]},
        modules={"/device:TPU:0": [("jit__step(1)", 0, US)]}))
    for metric in ("window_attn_roofline_pct.train",
                   "full_attn_roofline_pct.train",
                   "attn_window_time_pct.train", "attn_full_time_pct.train",
                   "moe_experts_roofline_pct.train_reglu"):
        assert readers(metric, context(**STEP)) is None


# -- the first step's distances, where a leaf takes no gradient ---------------------------


def test_first_step_distances_read_frozen_leaves_as_equal():
    """`reference_window.first_step_distances` on small trees: a sound step
    reads next to 0, an unchanged state 1, a gradient left out 1 on its
    leaf; a leaf whose gradient is zero on both sides (a frozen router)
    reads 0 in every comparison, not NaN, and infinity where only the
    reference's is zero."""
    import jax
    import math
    import numpy as np

    import reference_window as ref

    rng = np.random.default_rng(0)
    grads = {"head": rng.normal(size=(64, 8)).astype(np.float32),
             "layers": {"01E": {"w_up": rng.normal(size=(32,))
                                .astype(np.float32),
                                "router": np.zeros((4,), np.float32)}}}
    tree = jax.tree_util.tree_map
    before = tree(lambda g: rng.normal(size=g.shape).astype(np.float32),
                  grads)
    lr = 3e-4

    def distances(g, after=None):
        moment = tree(lambda x: (1 - ref.ADAM_B1) * x, g)
        after = after or tree(
            lambda p, x: p + ref.adam_first_step(x, lr), before, g)
        return ref.first_step_distances(
            tree(jax.numpy.asarray, before), after, moment, grads, lr)

    sound = distances(grads)
    for name in ("gradient", "update", "optimizer"):
        assert sound[name][0] < 1e-3
        assert sound[name][1]["['layers']['01E']['router']"] == 0.0
    assert sound["gradient_by_name"][1]["router"] == 0.0
    assert sound["gradient_by_name"][0] < 1e-3
    assert all(math.isfinite(v) for v in sound["gradient_by_name"][1].values())
    assert sound["flipped"][0] == 0.0
    assert distances(grads, after=before)["update"][0] == pytest.approx(1.0)
    lost = distances(dict(grads, head=np.zeros_like(grads["head"])))
    assert lost["gradient"][1]["['head']"] == pytest.approx(1.0)
    moved = tree(np.copy, grads)
    moved["layers"]["01E"]["router"] += 1.0  # a router that did learn
    got = distances(moved)
    assert got["gradient"][1]["['layers']['01E']['router']"] == math.inf
    assert got["gradient_by_name"][0] == math.inf


# -- the runner, end to end at toy widths -------------------------------------------------


@pytest.mark.parametrize("change, failing", [
    (None, ()),
    ("float8", ("grads_are_reference", "update_is_reference")),
    ("no_window", ("window_pairs_are_exact", "grads_are_reference")),
    ("no_routed_experts", ("grads_are_reference", "update_is_reference")),
])
def test_runner_train_window_end_to_end_at_toy_widths(tmp_path, monkeypatch,
                                                      change, failing):
    """Runner ``train_window`` through `run.run_cell` on a copy of
    ``benchmarks/`` with a toy configuration and cell dropped in as new
    files: the model by `resolve`, the reference by the name the
    configuration gives, every check of the real cell but the kernel's.
    Then the controls of ``control_window.py`` through the same comparison:
    each fault comes out ``correct: false`` by the checks named and no
    other."""
    import jax

    bdir = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, bdir,
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))

    def put(path, obj):
        with open(os.path.join(bdir, path), "w") as f:
            json.dump(obj, f)

    toy = dict(config(), vocab_size=256, hidden_size=64,
               num_attention_heads=14, num_key_value_heads=2, head_dim=16,
               sliding_window_size=40, router_width=16,
               moe_num_primary_experts=4, experts_first=4,
               moe_num_active_primary_experts=2, moe_ffn_hidden_size=32)
    put("configs/toy_window.json", toy)
    put("traffic/toy_b2_s128.json", dict(
        run.load_json(bdir, "traffic", f"{TRAFFIC}.json"),
        batch=2, seq_len=128))
    put("workloads/toy_window_train.json",
        run.load_json(bdir, "workloads", f"{CELL}.json"))
    bench = run.load_json(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "toy_window", "source": "none",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy_window.json"})
    bench["workloads"].append({"name": "toy_window_train", "chips": 1,
                               "config": "toy_window", "why": "toy",
                               "traffic": "toy_b2_s128"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy_window_train")
    # the limits are sized for the cell: 16,384 tokens a step and 24,576
    # assignments a layer. 256 tokens at toy widths average the bf16
    # rounding of far fewer logits, and a leaf name's gradient rests on some
    # tens of rows, one of which may go to another expert by a rounding
    import reference_window

    monkeypatch.setattr(reference_window, "LOSS_TOL", 5e-3)
    monkeypatch.setattr(reference_window, "GRAD_NAME_TOL", 0.3)
    # at 64 wide three window layers' attention is a larger share of the
    # parameters than at the cell, and their signs of the update's
    monkeypatch.setattr(reference_window, "UPDATE_TOL", 0.5)
    from edl_tpu.models import hybrid

    import control_window

    monkeypatch.setattr(hybrid, "_ROW_TILE", 64)
    for module, replacements in control_window.changes().get(
            change, {}).items():
        for name, replacement in replacements.items():
            monkeypatch.setattr(module, name, replacement)
    lines = []
    monkeypatch.setattr(run, "log", lines.append)
    run.validate(bench, bdir)
    out = run.run_cell(bench, bdir, "toy_window_train", 2**31 + 77, 1.5,
                       False, jax.devices()[:1])
    checks = next(line for line in lines if line.startswith("checks: "))
    for name in ("losses_finite", "no_compile_in_window", "no_rescale",
                 "loss_towards_log_vocab", "no_token_dropped",
                 "assignments_conserved", "first_loss_is_reference",
                 "window_pairs_are_exact", "grads_are_reference",
                 "update_is_reference", "optimizer_is_adam"):
        assert f"'{name}': {name not in failing}" in checks, (checks, lines)
    assert out["correct"] == (not failing)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}
    seen = [line for line in lines if line.startswith("pairs seen, layer")]
    assert len(seen) == 4
    inside = 2 * (40 * 41 // 2 + (128 - 40) * 40)
    if change == "no_window":  # the core's own count reads every causal pair
        assert all(f": {2 * 128 * 129 // 2} of" in line for line in seen)
    else:
        assert sum(f": {inside} of" in line for line in seen) == 3
    assert any("'router': 0.0" in line for line in lines)
