"""What PR 32 added to the benchmark for the sparse-attention family, on the
CPU: `benchmarks/costs_sparse.py` against hand-computed figures, the new
`BENCHMARK.json` entries, the configuration file against the catalog's
rules, the readers `mfu_sparse` and `scope_roofline_sparse` on a hand-built
trace, and runner `train_sparse` with its two-part comparison and the four
controls of `control_sparse.py` end to end at toy widths. No chip, no
timing."""

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

import costs_sparse  # noqa: E402
import run  # noqa: E402
import trace_scopes as ts  # noqa: E402
from cell import ReadContext  # noqa: E402

CELL = "train_keyevl2_1chip"
US = 1_000_000  # picoseconds in a microsecond
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return run.load_json(BENCH, "configs", "keye-vl-2.0-30b-a3b.json")


def sizes():
    return dict(run.model_kwargs(config()), seq_len=16384)


# -- costs, by hand --------------------------------------------------------------


def test_forward_flops_of_each_layer_kind_by_hand():
    per = costs_sparse.forward_flops_per_token(**sizes())
    # a query keeps t + 1 keys up to 2,048 and 2,048 after: 1,920.0625 a row
    kept = (2048 * 2049 / 2 + (16384 - 2048) * 2048) / 16384
    assert costs_sparse.kept_keys_mean(**sizes()) == kept == 1920.0625
    # S: q 4096, k and v 512 each, o 4096; scores and values over the
    # SELECTED keys at the query heads' 4096
    assert per["S"] == 2 * 2048 * (4096 + 1024) + 2 * 4096 * 2048 \
        + 4 * kept * 4096 == 69_207_040
    # its indexer: projections to 16 x 64, 64 and 16; scores of 16 heads of
    # 64 over the causal half of 16,384 keys
    assert per["indexer"] == 2 * 2048 * (1024 + 64 + 16) \
        + 0.5 * 2 * 16384 * 1024 == 21_299_200
    # E: router 128, and 8 x 16 / 128 of a token through gate, up and down
    assert per["E"] == 2 * 2048 * 128 + 1.0 * 6 * 2048 * 768 == 9_961_472
    assert per["head"] == 2 * 2048 * 18992
    # the indexer takes no gradient: forward alone
    total = 3 * (6 * per["S"] + 6 * per["E"] + per["head"]) \
        + 6 * per["indexer"]
    assert costs_sparse.train_flops_per_token(**sizes()) == total
    assert total == pytest.approx(1.7862e9, rel=1e-4)
    # the program's own count (Model.flops_per_step) is the same work
    from edl_tpu.models import resolve

    model = resolve("hybrid", sizes())
    assert model.flops_per_step(1) == pytest.approx(total * 16384)


def test_kernel_floors_by_hand():
    tokens, kw = 16384, sizes()
    kept = 1920.0625
    # the selected keys' attention is bound by compute
    assert costs_sparse.sparse_attn_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(3 * 6 * tokens * 4 * kept * 4096 / 197e12)
    assert costs_sparse.attn_forward_bytes_per_token_layer(**kw) \
        == (2 * 4096 + 2 * 512) * 2
    slow = dict(PEAKS, hbm_bytes_per_s=1e6)
    assert costs_sparse.sparse_attn_floor_seconds(tokens, slow, **kw) \
        == pytest.approx(3 * 6 * tokens * (2 * 4096 + 2 * 512) * 2 / 1e6)
    # the indexer's scores: once a step, forward, the causal half
    assert costs_sparse.indexer_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(6 * tokens * 0.5 * 2 * 16384 * 1024 / 197e12)
    assert costs_sparse.indexer_forward_bytes_per_token_layer(**kw) \
        == 17 * 64 * 2 + 16 * 4 + 4
    # the held experts: 16,384 assignments a step a layer, three matrices
    assert costs_sparse.held_assignments_per_token(**kw) == 1.0
    flops = 3 * 6 * tokens * 6 * 2048 * 768
    assert costs_sparse.experts_floor_seconds(tokens, PEAKS, **kw) \
        == pytest.approx(flops / 197e12)
    weights = 16 * 3 * 2048 * 768 * 2
    rows = tokens * 2 * (2048 + 768) * 2
    assert costs_sparse.experts_floor_seconds(tokens, slow, **kw) \
        == pytest.approx(6 * (4 * weights + 3 * rows) / 1e6)
    # and for the assignments a run counted
    assert costs_sparse.experts_floor_seconds(
        tokens, PEAKS, held_per_token=0.5, **kw) \
        == pytest.approx(max(flops / 2 / 197e12,
                             6 * (4 * weights + 3 * rows / 2) / 819e9))


# -- the contract and the configuration file -------------------------------------------

NEW = ("mfu_pct.train_sparse", "sparse_attn_roofline_pct.train",
       "indexer_roofline_pct.train", "indexer_time_pct.train",
       "select_time_pct.train", "moe_experts_roofline_pct.train_gated",
       "selected_keys_share.train")
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
    # the cell and its configuration stand third; PR 35 added one after them
    cell, later = bench["workloads"][2], 7  # PR 35's per-layer metrics
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "keye-vl-2.0-30b-a3b", "fixed_b1_s16384", 1)
    assert "1,024" in cell["why"] and "8,192" in cell["why"]
    entry = bench["configs"][2]
    cfg = run.load_json(REPO, entry["file"])
    assert entry["name"] == cell["config"]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    for e in bench["configs"] + bench["workloads"]:
        assert len(e["why"]) <= 200 and "\n" not in e["why"]
    reports = [m["name"] for m in bench["per_layer"] if run.reports(m, CELL)]
    assert reports == list(JOINED[:0]) + [
        m["name"] for m in bench["per_layer"]
        if m["name"] in JOINED or m["name"] in NEW]
    assert len(reports) == len(JOINED) + len(NEW) == 25
    # the new metrics stood at the end, in order, and list this cell alone
    mine = bench["per_layer"][-len(NEW) - later:-later]
    assert tuple(m["name"] for m in mine) == NEW
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
    # a metric the cell joined lists it after the cells it listed
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            assert m["workloads"].index(CELL) >= 1 and m["workloads"][-2:] \
                == [CELL, "train_smallthinker21b_1chip"]
    e2e = next(m for m in bench["end_to_end"]
               if m["name"] == "train_tokens_per_s")
    assert e2e["workloads"][:3] == ["train_gpt2m_1chip",
                                    "train_nemotron3nano_1chip", CELL]
    traffic = run.load_json(BENCH, "traffic", "fixed_b1_s16384.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["warmup_steps"],
            traffic["queue_ahead"], traffic["batches_per_shard"]) \
        == (1, 16384, 3, 6, 1)
    workload = run.load_json(BENCH, "workloads", f"{CELL}.json")
    assert (workload["runner"], workload["optimizer"], workload["remat"],
            workload["learning_rate"], workload["traced_steps"]) \
        == ("train_sparse", "adam", True, 3e-4, 3)


def test_the_configuration_keeps_every_published_number():
    """The catalog's rule: every number of the published config under its
    own key, unchanged unless the key is in ``reduced``; nested groups whole;
    ``reduced`` names no width."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    assert cfg["sa_config"] == row["config"]["sa_config"]
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"]
    # the keys the program reads are the published ones, or copies of them
    sa = cfg["sa_config"]
    assert (cfg["indexer_num_heads"], cfg["indexer_head_dim"],
            cfg["indexer_topk"]) == (sa["indexer_num_heads"],
                                     sa["indexer_head_dim"], sa["topk"])
    assert cfg["router_width"] == row["config"]["num_experts"] == 128
    assert cfg["layer_pattern"] == "SE" * cfg["num_hidden_layers"]
    kw = run.model_kwargs(cfg)
    assert (kw["d_model"], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"],
            kw["expert_width"], kw["top_k"], kw["rope_theta"],
            kw["norm_eps"], kw["expert_act"], kw["router_score"],
            kw["shared_width"], kw["experts_count"], kw["vocab_size"]) == (
        2048, 32, 4, 128, 768, 8, 1e7, 1e-6, "silu", "softmax", 0, 16, 18992)
    for key in ("qk_norm", "indexer_rotary_part", "indexer_layernorm",
                "indexer_scaling", "chunk_sizes", "initialiser"):
        assert cfg["assumed"][key]
    assert all(cfg[k] for k in ("deployment", "cut", "departures"))
    assert "NO effect" in cfg["assumed"]["chunk_sizes"]


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
    # attention 18,874,368 + two head norms 256 + indexer 2,260,992 + its
    # LayerNorm 128 + the pre-norm 2,048
    assert count(shapes["layers"]["00S"]) == 18_874_368 + 256 + 2_260_992 \
        + 128 + 2048 == 21_137_792
    # router 262,144 + 16 experts of 3 x 2048 x 768 + the pre-norm
    assert count(shapes["layers"]["01E"]) == 262_144 + 16 * 4_718_592 + 2048
    assert count(shapes) == cfg["parameters"] == 659_190_016
    assert "router_bias" not in shapes["layers"]["01E"]
    assert "shared_up" not in shapes["layers"]["01E"]


# -- the readers on a hand-built trace ---------------------------------------------------

FWD = "jit(_step)/fwd_bwd/jvp()/"
BWD = "jit(_step)/fwd_bwd/transpose(jvp())/checkpoint/"
#: (instruction, op_name, start us, duration us): one traced step
OPS = [
    ("fusion.1", FWD + "embed/gather", 0, 10),
    ("fusion.2", FWD + "attn/indexer/indexer_proj/dot_general", 10, 5),
    ("fusion.3", FWD + "attn/while/body/indexer/indexer_scores/dot_general", 15, 25),
    ("fusion.4", FWD + "attn/while/body/attn_select/reduce_sum", 40, 30),
    ("fusion.5", FWD + "attn/attn_proj/dot_general", 70, 10),
    ("flash_fwd.3[tpu_custom_call]", FWD + "attn/attn_core/flash_fwd/pallas_call", 80, 40),
    ("fusion.6", FWD + "moe/moe_route/top_k", 120, 5),
    ("ragged-dot-none.1[tpu_custom_call]", "ragged-dot-none", 125, 20),
    ("fusion.7", FWD + "moe/moe_experts/mul", 145, 5),
    ("flash_bwd_dq.4[tpu_custom_call]", BWD + "attn/attn_core/flash_bwd_dq/pallas_call", 150, 60),
    ("fusion.8", "jit(_step)/optimizer/add", 210, 10),
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
    assert readers("sparse_attn_roofline_pct.train", context(**STEP)) \
        == pytest.approx(100 * costs_sparse.sparse_attn_floor_seconds(
            tokens, PEAKS, **kw) / 100e-6)
    assert readers("indexer_roofline_pct.train", context(**STEP)) \
        == pytest.approx(100 * costs_sparse.indexer_floor_seconds(
            tokens, PEAKS, **kw) / 25e-6)
    assert readers("moe_experts_roofline_pct.train_gated",
                   context(moe_held_per_token=0.75, **STEP)) \
        == pytest.approx(100 * costs_sparse.experts_floor_seconds(
            tokens, PEAKS, held_per_token=0.75, **kw) / 25e-6)
    assert readers("indexer_time_pct.train", context()) \
        == pytest.approx(100 * 30 / busy)
    assert readers("select_time_pct.train", context()) \
        == pytest.approx(100 * 30 / busy)
    assert readers("mfu_pct.train_sparse", context(**STEP)) \
        == pytest.approx(100 * 16384 * costs_sparse.train_flops_per_token(
            **kw) / 197e12)
    assert readers("selected_keys_share.train",
                   context(selected_keys_share=0.2344)) == 0.2344
    # the metrics the cell joined read the same names here
    assert readers("attn_core_time_pct.train", context()) \
        == pytest.approx(100 * 100 / busy)
    assert readers("flash_time_pct.train_hybrid", context()) \
        == pytest.approx(100 * 100 / busy)
    assert readers("moe_time_pct.train", context()) \
        == pytest.approx(100 * 30 / busy)


def test_readers_find_nothing_where_there_is_nothing(readers, monkeypatch):
    for metric in ("mfu_pct.train_sparse", "sparse_attn_roofline_pct.train",
                   "selected_keys_share.train"):
        assert readers(metric, context()) is None  # the runner gave no values
    # a configuration without an indexer (the parent's cells): nothing
    hybrid = run.model_kwargs(run.load_json(
        BENCH, "configs", "nemotron-3-nano-30b-a3b.json"))
    assert readers("sparse_attn_roofline_pct.train",
                   context(hybrid, **STEP)) is None
    monkeypatch.setattr(ts, "current", lambda: ts.ScopedTrace(
        devices={"/device:TPU:0": [ts.Op("fusion.1", "jit(_step)/mlp", 0, US)]},
        modules={"/device:TPU:0": [("jit__step(1)", 0, US)]}))
    for metric in ("indexer_time_pct.train", "select_time_pct.train",
                   "indexer_roofline_pct.train",
                   "moe_experts_roofline_pct.train_gated"):
        assert readers(metric, context(**STEP)) is None  # no such scope


# -- the first step's distances, summed where the trees lie -----------------------------


@pytest.mark.parametrize("placed", ["host", "device", "mixed"])
def test_first_step_distances_on_small_trees(placed):
    """`reference_sparse.first_step_distances` makes a leaf's sums in one
    jitted program, so the cell's trees can stay on the chip: with the trees
    on the host, on the device, or two of each (what the runner hands it),
    it reads what `reference_hybrid`'s numpy reads on the same trees; an
    unchanged state reads 1, a gradient left out reads 1 on its leaf, a
    zero gradient met by zeros reads 0."""
    import jax
    import numpy as np

    import reference_hybrid
    import reference_sparse as ref

    rng = np.random.default_rng(0)
    grads = {"a": rng.normal(size=(64, 8)).astype(np.float32),
             "layers": {"00S": {"wq": rng.normal(size=(32,))
                                .astype(np.float32),
                                "ix_wq": np.zeros((4,), np.float32)}}}
    tree = jax.tree_util.tree_map
    before = tree(lambda g: rng.normal(size=g.shape).astype(np.float32),
                  grads)
    lr = 3e-4

    def put(t, on_device):
        return tree(jax.numpy.asarray, t) if on_device else t

    def distances(g, after=None):
        moment = tree(lambda x: (1 - ref.ADAM_B1) * x, g)
        after = after or tree(
            lambda p, x: p + ref.adam_first_step(x, lr), before, g)
        host = (before, after, moment, grads)
        where = {"host": (0, 0, 0, 0), "device": (1, 1, 1, 1),
                 "mixed": (1, 0, 0, 1)}[placed]
        got = ref.first_step_distances(
            *(put(t, d) for t, d in zip(host, where)), lr)
        want = reference_hybrid.first_step_distances(*host, lr)
        for name in ("gradient", "update", "optimizer"):
            assert got[name][0] == pytest.approx(want[name][0], rel=1e-4,
                                                 abs=1e-6)
            for leaf, far in want[name][1].items():
                assert got[name][1][leaf] == pytest.approx(far, rel=1e-4,
                                                           abs=1e-6)
        return got

    sound = distances(grads)
    assert all(sound[k][0] < 1e-3 for k in ("gradient", "update",
                                            "optimizer"))
    assert sound["gradient"][1]["['layers']['00S']['ix_wq']"] == 0.0
    assert sound["gradient_by_name"][1]["ix_wq"] == 0.0
    assert sound["flipped"][0] == 0.0
    still = distances(grads, after=before)
    assert still["update"][0] == pytest.approx(1.0)
    lost = distances(tree(lambda x: x, dict(grads, a=np.zeros_like(
        grads["a"]))))
    assert lost["gradient"][1]["['a']"] == pytest.approx(1.0)
    assert lost["gradient_by_name"] == (pytest.approx(1.0),
                                        lost["gradient_by_name"][1])
    flipped = tree(np.copy, grads)
    flipped["a"].reshape(-1)[::100] *= -1
    share = (flipped["a"] != grads["a"]).mean()
    got = distances(flipped)
    assert got["update"][1]["['a']"] == pytest.approx(2 * share ** 0.5,
                                                      rel=1e-3)
    assert got["flipped"][0] == pytest.approx(
        (flipped["a"] != grads["a"]).sum() / (64 * 8 + 32 + 4))


# -- the runner, end to end at toy widths -------------------------------------------------


@pytest.mark.parametrize("change, failing", [
    (None, ()),
    ("float8", ("grads_are_reference", "update_is_reference",
                "selection_is_reference")),
    ("recent_selection", ("selection_is_reference",)),
    ("no_selection", ("selection_is_reference",)),
    ("no_routed_experts", ("grads_are_reference", "update_is_reference")),
])
def test_runner_train_sparse_end_to_end_at_toy_widths(tmp_path, monkeypatch,
                                                      change, failing):
    """Runner ``train_sparse`` through `run.run_cell` on a copy of
    ``benchmarks/`` with a toy configuration and cell dropped in as new
    files: the model by `resolve`, the reference by the name the
    configuration gives, every check of the real cell but the kernel's.
    Then the controls of ``control_sparse.py`` through the same comparison:
    each fault comes out ``correct: false`` by the checks named and no
    other."""
    import jax

    bdir = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, bdir,
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))

    def put(path, obj):
        with open(os.path.join(bdir, path), "w") as f:
            json.dump(obj, f)

    toy = dict(config(), vocab_size=256, hidden_size=64, layer_pattern="SESE",
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               indexer_num_heads=4, indexer_head_dim=16, indexer_topk=32,
               router_width=16, num_experts=4, experts_first=4,
               num_experts_per_tok=2, moe_intermediate_size=32)
    put("configs/toy_sparse.json", toy)
    put("traffic/toy_b2_s128.json", dict(
        run.load_json(bdir, "traffic", "fixed_b1_s16384.json"),
        batch=2, seq_len=128))
    put("workloads/toy_sparse_train.json",
        run.load_json(bdir, "workloads", f"{CELL}.json"))
    bench = run.load_json(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "toy_sparse", "source": "none",
                             "reduced": [], "why": "toy",
                             "file": "benchmarks/configs/toy_sparse.json"})
    bench["workloads"].append({"name": "toy_sparse_train", "chips": 1,
                               "config": "toy_sparse", "why": "toy",
                               "traffic": "toy_b2_s128"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy_sparse_train")
    # the limits are sized for the cell: 16,384 tokens a step, rows of
    # 16,384 scores, 16,384 assignments a layer. 256 tokens at toy widths
    # average the bf16 rounding of far fewer logits, one key of a row of 32
    # is 3% of it, and a leaf name's gradient rests on some tens of rows
    import reference_sparse

    monkeypatch.setattr(reference_sparse, "LOSS_TOL", 5e-3)
    monkeypatch.setattr(reference_sparse, "GRAD_NAME_TOL", 0.3)
    monkeypatch.setattr(reference_sparse, "SELECT_DIFFER_TOL", 0.1)
    monkeypatch.setattr(reference_sparse, "SELECT_BAND_TOL", 0.1)
    from edl_tpu.models import hybrid

    import control_sparse

    monkeypatch.setattr(hybrid, "_ROW_TILE", 64)
    for name, replacement in control_sparse.changes().get(change, {}).items():
        monkeypatch.setattr(hybrid, name, replacement)
    lines = []
    monkeypatch.setattr(run, "log", lines.append)
    run.validate(bench, bdir)
    out = run.run_cell(bench, bdir, "toy_sparse_train", 2**31 + 77, 1.5,
                       False, jax.devices()[:1])
    checks = next(line for line in lines if line.startswith("checks: "))
    for name in ("losses_finite", "no_compile_in_window", "no_rescale",
                 "loss_towards_log_vocab", "no_token_dropped",
                 "assignments_conserved", "first_loss_is_reference",
                 "selection_is_reference", "grads_are_reference",
                 "update_is_reference", "optimizer_is_adam"):
        assert f"'{name}': {name not in failing}" in checks, (checks, lines)
    assert out["correct"] == (not failing)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert any(line.startswith("selection, layer 00S: selected")
               for line in lines)
    if change is None:
        assert any("in the future 0, rows miscounted 0" in line
                   for line in lines)
    if change == "no_selection":
        assert any("rows miscounted 192" in line for line in lines)
