"""The reduction behind ISSUE 25's per-layer metrics, on a hand-built trace:
`benchmarks/trace_scopes.py` (phases, kernels by name, idle gaps by host
span), `benchmarks/costs_attn.py`, and the five new readers as `run.py`
loads them, with every new metric's data file. No chip, no timing: counts
and arithmetic on a trace whose numbers are known."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

import costs_attn  # noqa: E402
import run  # noqa: E402
import trace_scopes as ts  # noqa: E402
from cell import ReadContext  # noqa: E402
from edl_tpu.obs.tracing import Span  # noqa: E402

US = 1_000_000  # picoseconds in a microsecond

FWD = "jit(_step)/fwd_bwd/jvp()/while/body/closed_call/"
BWD = "jit(_step)/fwd_bwd/transpose(fwd_bwd)/jvp()/while/body/closed_call/checkpoint/"

#: one traced step on the device, in microseconds: (instruction text,
#: op_name, start, duration). Two `while` loops hold their bodies; the
#: device is idle from 100 to 110 (between the loops) and from 190 to 202.
DEVICE = [
    ("%while.1 = (s32[]) while(%t), body=%b", "", 0, 100),
    ("%fusion.1 = bf16[8] fusion(%p)", FWD + "attn_proj/dot_general", 0, 10),
    ('%flash_fwd.5 = bf16[8] custom-call(%q), custom_call_target="tpu_custom_call"',
     FWD + "attn_core/flash_fwd/cond/branch_0_fun/flash_fwd/pallas_call", 10, 30),
    ("%copy.2 = bf16[8] copy(%o)", FWD + "attn_core/transpose", 40, 5),
    ("%fusion.2 = bf16[8] fusion(%p)", FWD + "mlp/dot_general", 45, 15),
    ('%flash_fwd.5 = bf16[8] custom-call(%q), custom_call_target="tpu_custom_call"',
     FWD + "attn_core/flash_fwd/cond/branch_0_fun/flash_fwd/pallas_call", 60, 30),
    ("%fusion.2 = bf16[8] fusion(%p)", FWD + "mlp/dot_general", 90, 10),
    ("%while.2 = (s32[]) while(%t), body=%b", "", 110, 80),
    ('%flash_fwd.6 = bf16[8] custom-call(%q), custom_call_target="tpu_custom_call"',
     BWD + "rematted_computation/attn_core/flash_fwd/cond/branch_0_fun/flash_fwd/pallas_call", 110, 30),
    # XLA drops the head of some names; the marker still stands
    ("%reduce.1 = f32[8] reduce(%x)", "checkpoint/rematted_computation/reduce_sum", 140, 2),
    # an unnamed kernel under the scope (the kernel's own name did not
    # reach the instruction): the named scope still finds it
    ('%branch_0_fun.7 = bf16[8] custom-call(%q), custom_call_target="tpu_custom_call"',
     BWD + "attn_core/flash_bwd_dq/cond/branch_0_fun/flash_bwd_dq/pallas_call", 142, 20),
    # a named kernel whose op_name was lost: the instruction's name finds it
    ('%flash_bwd_dkv.9 = bf16[8] custom-call(%q), custom_call_target="tpu_custom_call"', "", 162, 18),
    ("%fusion.3 = f32[8] fusion(%g)", BWD + "mlp/transpose", 180, 10),
    ("%fusion.4 = f32[8] fusion(%g)", "jit(_step)/optimizer/add", 202, 6),
    ("%copy-done.1 = f32[8] copy-done(%c)", "", 208, 2),
]
MODULES = [("jit__step(1)", 0, 210)]
#: two host threads, microseconds. The worker waits in loss_sync over the
#: first gap; the second gap is partly inside the pump's `place`, nested in
#: nothing, and mostly under no span at all.
HOST = {
    "python": [("worker_step", 0, 150), ("step_dispatch", 1, 4),
               ("PjitFunction(_step)", 2, 2), ("loss_sync", 6, 140)],
    "python ": [("lease", 150, 3), ("place", 185, 10)],
}


def xspace_text() -> str:
    meta, events, hosts = {}, [], []

    def mid(table, key):
        return table.setdefault(key, len(table) + 1)

    for text, op_name, start, dur in DEVICE:
        events.append(f"events {{ metadata_id: {mid(meta, (text, op_name))} "
                      f"offset_ps: {start * US} duration_ps: {dur * US} }}")
    out = ['planes { id: 1 name: "/device:TPU:0"',
           'stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }',
           'stat_metadata { key: 2 value { id: 2 name: "tf_op" } }']
    for (text, op_name), i in meta.items():
        stat = f'stats {{ metadata_id: 2 str_value: "{op_name}:" }}' \
            if op_name else ""
        out.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: {json.dumps(text)} "
                   f'stats {{ metadata_id: 1 str_value: "fusion" }} {stat} }} }}')
    out.append('event_metadata { key: 99 value { id: 99 name: "jit__step(1)" } }')
    out.append('lines { id: 1 name: "XLA Ops" timestamp_ns: 1000 '
               + " ".join(events) + " }")
    out.append('lines { id: 2 name: "XLA Modules" timestamp_ns: 1000 events '
               f"{{ metadata_id: 99 offset_ps: 0 duration_ps: {210 * US} }} }}")
    out.append('lines { id: 3 name: "Steps" timestamp_ns: 1000 events '
               f"{{ metadata_id: 99 offset_ps: 0 duration_ps: {210 * US} }} }} }}")
    names = {}
    for line, (thread, spans) in enumerate(HOST.items(), 1):
        ev = " ".join(
            f"events {{ metadata_id: {mid(names, name)} "
            f"offset_ps: {start * US} duration_ps: {dur * US} }}"
            for name, start, dur in spans)
        hosts.append(f'lines {{ id: {line} name: "{thread.strip()}" '
                     f"timestamp_ns: 1000 {ev} }}")
    out.append('planes { id: 2 name: "/host:CPU"')
    out += [f"event_metadata {{ key: {i} value {{ id: {i} "
            f"name: {json.dumps(name)} }} }}" for name, i in names.items()]
    out += hosts + ["}"]
    return "\n".join(out)


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return ts.from_xspace(
        ProfileData.text_proto_to_serialized_xspace(xspace_text()))


@pytest.fixture
def readers(trace, monkeypatch):
    """The readers as run.py loads them, reading the hand-built trace."""
    monkeypatch.setattr(ts, "current", lambda: trace)

    def read(metric, ctx):
        how = run.load_json(BENCH, "layer_metrics", f"{metric}.json")
        reader = run.load_module(BENCH, "readers", how["reader"])
        return reader.read(ctx, **how.get("args", {}))

    return read


def context(spans=(), **values):
    return ReadContext(
        spans=list(spans), values=values, trace=None,
        device={"kind": "TPU v5 lite"}, chips=1,
        model_kwargs=dict(vocab_size=50257, d_model=1024, n_layers=24,
                          n_heads=16, d_ff=4096, seq_len=1024))


# -- the adapter --------------------------------------------------------------------


def test_adapter_reads_op_names_from_the_events_metadata(trace):
    ops = trace.devices["/device:TPU:0"]
    assert len(ops) == len(DEVICE)
    flash = [op for op in ops if op.name.startswith("flash_fwd.5")]
    assert flash[0].name == "flash_fwd.5[tpu_custom_call]"
    assert flash[0].op_name.endswith("flash_fwd/pallas_call")  # no colon
    assert (flash[0].start, flash[0].dur) == (11 * US, 30 * US)  # whole ps
    assert [op.op_name for op in ops if op.name.startswith("while")] == ["", ""]
    assert trace.modules["/device:TPU:0"] == [("jit__step(1)", US, 210 * US)]
    assert {name for name, _, _ in trace.host} == {
        "worker_step", "step_dispatch", "PjitFunction(_step)", "loss_sync",
        "lease", "place"}
    assert sorted(h[0] for h in trace.host_spans({"lease", "place", "x"})) \
        == ["lease", "place"]


def test_agrees_with_trace_reduce_on_busy_time(trace):
    import trace_reduce
    from jax.profiler import ProfileData

    ref = trace_reduce.from_profile(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(xspace_text())))
    ops = trace.devices["/device:TPU:0"]
    assert sum(own for _, own in ts.self_times(ops)) == 188 * US
    assert ref.busy_s == pytest.approx(188 * US * ts.PS)


# -- phases --------------------------------------------------------------------------


@pytest.mark.parametrize("op_name, phase", [
    (FWD + "mlp/dot_general", "forward"),
    ("jit(_step)/fwd_bwd/jvp()/while", "forward"),
    (BWD + "mlp/transpose", "backward"),
    ("jit(_step)/fwd_bwd/transpose(fwd_bwd)/jvp()/add_any", "backward"),
    ("checkpoint/attn_core/reduce_sum", "backward"),
    (BWD + "rematted_computation/mlp/dot_general", "recompute"),
    ("checkpoint/rematted_computation/reduce_sum", "recompute"),
    ("jit(_step)/optimizer/add", "optimizer"),
    ("jit(_step)/jvp()/while/body/mlp/transpose", "other"),  # an unnamed step
    ("", "other"),
])
def test_phase_of_an_op_name(op_name, phase):
    assert ts.phase_of(op_name) == phase


def test_phase_seconds_are_self_seconds_and_sum_to_the_busy_time(trace):
    ops = trace.devices["/device:TPU:0"]
    by = ts.time_by(ops, lambda op: ts.phase_of(op.op_name))
    # the whiles' own time is nothing: their bodies fill them
    assert {k: v / US for k, v in by.items()} == {
        "forward": 100, "recompute": 32, "backward": 30, "optimizer": 6,
        "other": 20}  # the kernel that lost its op_name (18) and a copy-done
    assert sum(by.values()) == 188 * US


def test_phase_share_metrics(readers):
    got = {m: readers(m, context()) for m in (
        "fwd_time_pct.train", "recompute_time_pct.train",
        "bwd_time_pct.train", "optimizer_time_pct.train")}
    assert got == {
        "fwd_time_pct.train": pytest.approx(100 * 100 / 188),
        "recompute_time_pct.train": pytest.approx(100 * 32 / 188),
        "bwd_time_pct.train": pytest.approx(100 * 30 / 188),
        "optimizer_time_pct.train": pytest.approx(100 * 6 / 188)}


# -- kernels by name -------------------------------------------------------------


def test_scope_calls_finds_a_kernel_by_either_route(trace):
    ops = trace.devices["/device:TPU:0"]
    def us(scope):
        return [c / US for c in ts.scope_calls(ops, scope)]

    assert us("flash_fwd") == [30, 30, 30]  # forward twice, remat once
    assert us("flash_bwd_dq") == [20]   # by the named scope alone
    assert us("flash_bwd_dkv") == [18]  # by the instruction's name alone
    # neighbours under one scope are one call
    assert us("attn_core") == [35, 30, 30, 20]
    assert us("mlp") == [15, 10, 10]
    assert us("no_such_scope") == []


def test_kernel_call_metrics(readers):
    assert readers("flash_fwd_call_ms_p50.train", context()) \
        == pytest.approx(0.030)
    assert readers("flash_dq_call_ms_p50.train", context()) \
        == pytest.approx(0.020)
    assert readers("flash_dkv_call_ms_p50.train", context()) \
        == pytest.approx(0.018)


def test_attention_work_is_the_records_figure():
    """PERF.md: one forward call over (512, 1024, 64), 32,768 tokens at
    d_model 1024, is 68.7 GFLOP and 268 MB."""
    tokens = 32 * 1024
    assert tokens * costs_attn.forward_flops_per_token_layer(1024, 1024) \
        == pytest.approx(68.7e9, rel=2e-3)
    assert tokens * costs_attn.forward_bytes_per_token_layer(1024) \
        == pytest.approx(268e6, rel=2e-3)
    kw = dict(d_model=1024, n_layers=24, seq_len=1024, vocab_size=1, d_ff=1)
    assert costs_attn.train_flops_per_token(**kw) \
        == 3 * 24 * costs_attn.forward_flops_per_token_layer(1024, 1024)
    # as costs.train_flops_per_token counts attention: the same work as MFU
    import costs
    both = dict(kw, vocab_size=50257, d_ff=4096)
    without = costs.train_flops_per_token(**dict(both, seq_len=0))
    assert costs.train_flops_per_token(**both) - without \
        == pytest.approx(costs_attn.train_flops_per_token(**kw))
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    floor = costs_attn.floor_seconds(tokens, peaks, **kw)
    assert floor == pytest.approx(25.1e-3, rel=5e-3)  # compute bound
    slow_hbm = dict(peaks, hbm_bytes_per_s=100e9)
    assert costs_attn.floor_seconds(tokens, slow_hbm, **kw) \
        == pytest.approx(tokens * 24 * 12 * 1024 * 2 / 100e9)


def test_attn_roofline_is_the_floor_over_the_seconds_under_the_scope(
        readers, trace):
    ops = trace.devices["/device:TPU:0"]
    assert ts.steps_with(ops, trace.modules["/device:TPU:0"], "attn_core") == 1
    assert ts.steps_with(ops, trace.modules["/device:TPU:0"], "nope") == 0
    ctx = context(steady_tokens_per_s=16384.0, step_s_p50=2.0)
    floor = costs_attn.floor_seconds(
        32768.0, {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        **ctx.model_kwargs)
    # under attn_core: two forward calls and the copy, the recomputed
    # forward, dq; not the kernel that lost its op_name
    assert readers("attn_roofline_pct.train", ctx) \
        == pytest.approx(100 * floor / 115e-6)
    assert readers("attn_roofline_pct.train", context()) is None  # no rate


# -- idle gaps and host spans ---------------------------------------------------


def test_gaps_and_their_attribution(trace):
    ops = trace.devices["/device:TPU:0"]
    idle = ts.gaps(ops)
    assert idle == [(101 * US, 111 * US), (191 * US, 203 * US)]
    host = trace.host_spans({"worker_step", "step_dispatch", "loss_sync",
                             "lease", "place"})
    by, longest = ts.attribute(idle, host)
    # innermost wins: loss_sync inside worker_step; the pump's place covers
    # part of the second gap and nothing covers the rest
    assert by == {"loss_sync": 10 * US, "place": 5 * US, "none": 7 * US}
    assert longest == [(12 * US, "none"), (10 * US, "loss_sync")]
    assert ts.attribute(idle, [])[0] == {"none": 22 * US}


def test_idle_attributed_metric_and_its_printed_gaps(readers, capsys):
    spans = [SimpleNamespace(name=n) for n in
             ("worker_step", "loss_sync", "lease", "place", "first_step")]
    assert readers("idle_attributed_pct.train", context(spans)) \
        == pytest.approx(100 * 15 / 22)
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("idle seconds by host span: ")
    assert json.loads(out[0].split(": ", 1)[1]) == {
        "loss_sync": pytest.approx(10e-6), "place": pytest.approx(5e-6),
        "none": pytest.approx(7e-6)}
    assert out[1].startswith("longest idle gaps")
    assert json.loads(out[1].split(": ", 1)[1]) == [
        [pytest.approx(12e-6), "none"], [pytest.approx(10e-6), "loss_sync"]]
    # a program that mirrors no span into the trace: nothing to read
    assert readers("idle_attributed_pct.train",
                   context([SimpleNamespace(name="restore")])) is None


# -- spans ------------------------------------------------------------------------


def test_span_self_time_is_the_span_less_its_named_children(readers):
    def step(t0, wait, sync):
        ws = Span("worker_step", t0, t0 + 0.001 + wait + sync)
        return [ws,
                Span("input_wait", t0, t0 + wait, parent=ws),
                Span("loss_sync", t0 + wait, t0 + wait + sync, parent=ws),
                Span("lease", t0, t0 + 0.5)]  # the pump's: nobody's child

    spans = step(0.0, 0.0002, 2.0) + step(3.0, 0.0004, 2.1) \
        + step(6.0, 0.0003, 2.2)
    ctx = context(spans)
    assert readers("host_step_ms_p50.train", ctx) \
        == pytest.approx(1.3)  # 1 ms of its own and the median wait
    assert readers("input_wait_ms_p50.train", ctx) == pytest.approx(0.3)
    assert readers("lease_rpc_ms_p50.train", ctx) == pytest.approx(500.0)
    reader = run.load_module(BENCH, "readers", "span_self_stat")
    assert reader.read(ctx, span="worker_step", scale=1000.0) \
        == pytest.approx(1.0)  # every child taken out
    # spans of a program without `parent` (or no such span): nothing, no raise
    bare = [SimpleNamespace(name="lm_decode_step", start=0.0, end=1.0)]
    assert reader.read(context(bare), span="worker_step") is None
    assert reader.read(context(bare), span="lm_decode_step") == 1.0


def test_metrics_read_nothing_and_do_not_raise_without_names(monkeypatch):
    """The parent program's trace: no scope, no kernel name, no mirrored
    span. Every new metric is left out of the line."""
    bare = ts.ScopedTrace(
        devices={"/device:TPU:0": [
            ts.Op("while.9", "", 0, 10 * US),
            ts.Op("branch_0_fun.34[tpu_custom_call]",
                  "jit(_step)/jvp()/while/body/pallas_call", 0, 5 * US)]},
        modules={"/device:TPU:0": [("jit__step(1)", 0, 10 * US)]},
        host=[("PjitFunction(_step)", 0, US)])
    for current in (lambda: bare, lambda: None):
        monkeypatch.setattr(ts, "current", current)
        ctx = context([SimpleNamespace(name="restore", start=0.0, end=1.0)],
                      steady_tokens_per_s=1.0, step_s_p50=1.0)
        for m in run.load_json(REPO, "BENCHMARK.json")["per_layer"][4:]:
            how = run.load_json(BENCH, "layer_metrics", f"{m['name']}.json")
            reader = run.load_module(BENCH, "readers", how["reader"])
            assert reader.read(ctx, **how.get("args", {})) is None, m["name"]


def test_current_finds_the_cells_trace_and_parses_it_once(tmp_path,
                                                          monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(ts, "HERE", str(tmp_path))
    monkeypatch.setattr(ts, "_LOADED", {})
    assert ts.current() is None
    logdir = tmp_path / ".work" / "a_cell" / "trace" / "plugins" / "profile" / "t0"
    logdir.mkdir(parents=True)
    (logdir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(xspace_text()))
    first = ts.current()
    assert len(first.devices["/device:TPU:0"]) == len(DEVICE)
    assert ts.current() is first


def test_the_new_entries_are_additions_and_valid():
    bench = run.load_json(REPO, "BENCHMARK.json")
    run.validate(bench, BENCH)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:4] == ["step_ms_p50.train", "mfu_pct.train",
                         "flash_time_pct.train", "device_idle_pct.train"]
    # PR 25's twelve follow PR 24's four; later PRs append after them and
    # append their cells to a metric's `workloads`
    assert len(names) >= 16 and len(set(names)) == len(names)
    for m in bench["per_layer"][4:16]:
        assert m["workloads"][0] == "train_gpt2m_1chip"
        assert m["moves"] == "train_tokens_per_s"
    stage = run.load_json(BENCH, "layer_metrics",
                          "decode_stage_ms_p50.serve.json")
    assert stage["args"]["span"] == "lm_stage" \
        and "decode_stage_ms_p50.serve" not in names
