"""Telemetry-plane unit tests: registry/exposition round-trip, tracer and
rescale-timeline stitching, the stdlib HTTP endpoints, the coordinator
status bridge, structured logging, the collector's coordinator-health
block, and the `edl-tpu status` subcommand.

Everything here uses PRIVATE MetricsRegistry/Tracer instances — the
process-wide defaults stay untouched so these tests cannot contaminate
(or be contaminated by) the instrumented runtime code under test
elsewhere in the suite.
"""

import contextlib
import io
import json
import logging
import math
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import pytest

from edl_tpu.controller import FakeCluster, JobStore, NodeInfo
from edl_tpu.api import ResourceList
from edl_tpu.obs.bridge import CoordinatorStatusBridge
from edl_tpu.obs.http import MetricsServer, scrape_metrics
from edl_tpu.obs.logs import JsonLogFormatter, configure_logging
from edl_tpu.obs.metrics import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    parse_prometheus,
)
from edl_tpu.obs.tracing import (
    RESCALE_PHASES,
    Span,
    Tracer,
    load_spans,
    rescale_timeline,
    rescale_trace_id,
)
from edl_tpu.tools.collector import Collector


# -- registry ------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("t_gauge")
    g.set(7.0)
    g.inc(-2.0)  # gauges may go down
    assert g.value() == 5.0

    h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(30.0)
    cell = h.cell()
    assert cell["count"] == 3.0
    assert cell["sum"] == pytest.approx(30.55)


def test_registry_get_or_create_shares_instruments():
    reg = MetricsRegistry()
    a = reg.counter("shared_total", "one")
    b = reg.counter("shared_total", "ignored on re-get")
    assert a is b
    a.inc()
    assert b.value() == 1.0
    # name collisions across kind or labelset are refused, not silently merged
    with pytest.raises(ValueError):
        reg.gauge("shared_total")
    with pytest.raises(ValueError):
        reg.counter("shared_total", labelnames=("op",))


def test_labels_must_match_declaration():
    reg = MetricsRegistry()
    c = reg.counter("lbl_total", labelnames=("op",))
    c.inc(op="a")
    c.inc(2, op="b")
    assert c.value(op="a") == 1.0
    assert c.value(op="b") == 2.0
    with pytest.raises(ValueError):
        c.inc()  # missing declared label
    with pytest.raises(ValueError):
        c.inc(op="a", extra="x")


def test_render_parse_roundtrip():
    reg = MetricsRegistry()
    reg.counter("rt_ops_total", "ops by kind", labelnames=("kind",)).inc(
        3, kind="write"
    )
    reg.gauge("rt_depth", "queue depth").set(4.0)
    h = reg.histogram("rt_lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    h.observe(0.005)
    h.observe(0.05)
    h.observe(5.0)

    text = reg.render_prometheus()
    fams = parse_prometheus(text)

    assert fams["rt_ops_total"]["kind"] == "counter"
    assert fams["rt_ops_total"]["samples"]['rt_ops_total{kind="write"}'] == 3.0
    assert fams["rt_depth"]["samples"]["rt_depth"] == 4.0

    hist = fams["rt_lat_seconds"]
    assert hist["kind"] == "histogram"
    # cumulative buckets: 0.005 <= 0.01; 0.05 adds at le=0.1; 5.0 only at +Inf
    assert hist["samples"]['rt_lat_seconds_bucket{le="0.01"}'] == 1.0
    assert hist["samples"]['rt_lat_seconds_bucket{le="0.1"}'] == 2.0
    assert hist["samples"]['rt_lat_seconds_bucket{le="1"}'] == 2.0
    assert hist["samples"]['rt_lat_seconds_bucket{le="+Inf"}'] == 3.0
    assert hist["samples"]["rt_lat_seconds_count"] == 3.0
    assert hist["samples"]["rt_lat_seconds_sum"] == pytest.approx(5.055)


def test_label_values_escaped():
    reg = MetricsRegistry()
    g = reg.gauge("esc", labelnames=("path",))
    g.set(1.0, path='a"b\\c\nd')
    text = reg.render_prometheus()
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    fams = parse_prometheus(text)  # and the escaped line still parses
    assert any(v == 1.0 for v in fams["esc"]["samples"].values())


def test_parse_rejects_garbage():
    with pytest.raises(ValueError):
        parse_prometheus("this is not exposition format\n")
    with pytest.raises(ValueError):
        parse_prometheus('unbalanced}bracket{ 1\n')


def test_collector_callback_runs_at_scrape_time():
    reg = MetricsRegistry()
    g = reg.gauge("pulled")
    calls = []

    def collect():
        calls.append(1)
        g.set(float(len(calls)))

    reg.register_collector(collect)
    assert parse_prometheus(reg.render_prometheus())["pulled"]["samples"][
        "pulled"
    ] == 1.0
    reg.snapshot()
    assert len(calls) == 2
    reg.unregister_collector(collect)
    reg.render_prometheus()
    assert len(calls) == 2


def test_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("s_total").inc(2)
    h = reg.histogram("s_seconds")
    h.observe(0.2)
    snap = reg.snapshot()
    assert snap["s_total"]["samples"] == [{"labels": {}, "value": 2.0}]
    assert snap["s_seconds"]["samples"][0]["count"] == 1
    assert snap["s_seconds"]["samples"][0]["sum"] == pytest.approx(0.2)
    assert len(DEFAULT_BUCKETS) > 5  # sanity: default latency buckets exist


# -- tracer + timeline ---------------------------------------------------------


def test_tracer_record_find_and_positive_clamp():
    tr = Tracer(component="worker")
    s = tr.record("drain", 100.0, 100.5, trace_id="rescale-e000007")
    assert s.seconds == pytest.approx(0.5)
    # zero/negative intervals clamp to strictly positive: "it happened"
    z = tr.record("checkpoint", 100.5, 100.5, trace_id="rescale-e000007")
    assert z.seconds > 0.0
    assert len(tr.find(trace_id="rescale-e000007")) == 2
    assert tr.find(name="drain")[0].component == "worker"
    assert tr.find(trace_id="other") == []


def test_tracer_span_context_and_event():
    tr = Tracer(component="controller")
    with tr.span("actuate", trace_id="t1", job="j"):
        pass
    with pytest.raises(RuntimeError):
        with tr.span("actuate", trace_id="t1"):
            raise RuntimeError("boom")
    spans = tr.find(name="actuate")
    assert len(spans) == 2
    assert spans[1].attrs["error"] == "RuntimeError"
    ev = tr.event("decided", trace_id="t1")
    assert ev.seconds >= 0.0


def test_tracer_sink_jsonl_and_load_spans(tmp_path):
    path = tmp_path / "events.jsonl"
    with open(path, "w") as sink:
        tr = Tracer(component="worker", sink=sink)
        tr.record("restore", 10.0, 11.0, trace_id="rescale-e000003")
        # foreign lines interleave in a shared pod stream; loader skips them
        sink.write('{"kind": "profiler_step", "seconds": 0.1}\n')
        sink.write("not json at all\n")
        tr.record("first_step", 11.0, 11.2, trace_id="rescale-e000003")
    spans = load_spans(str(path))
    assert [s["name"] for s in spans] == ["restore", "first_step"]
    assert all(s["kind"] == "span" for s in spans)
    assert spans[0]["seconds"] == pytest.approx(1.0)


def test_rescale_timeline_stitches_components_and_dedupes():
    tid = rescale_trace_id(4)
    assert tid == "rescale-e000004"
    spans = [
        # controller side observed the actuation
        dict(kind="span", name="actuate", start=0.0, end=0.1, seconds=0.1,
             trace_id=tid, component="controller"),
        # worker side: both sides timed "restore"; longest wins, repeat counted
        dict(kind="span", name="restore", start=1.0, end=1.5, seconds=0.5,
             trace_id=tid, component="worker"),
        dict(kind="span", name="restore", start=1.0, end=1.2, seconds=0.2,
             trace_id=tid, component="worker"),
        dict(kind="span", name="first_step", start=2.0, end=2.3, seconds=0.3,
             trace_id=tid, component="worker"),
        # unrelated trace and an id-less span are excluded
        dict(kind="span", name="restore", start=0.0, end=9.0, seconds=9.0,
             trace_id="rescale-e000009", component="worker"),
        dict(kind="span", name="stray", start=0.0, end=1.0, seconds=1.0,
             trace_id="", component="worker"),
    ]
    out = rescale_timeline(spans, trace_id=tid)
    assert set(out) == {tid}
    t = out[tid]
    assert t["components"] == ["controller", "worker"]
    assert t["span_count"] == 4
    assert t["phases"]["restore"]["seconds"] == pytest.approx(0.5)
    assert t["phases"]["restore"]["count"] == 2
    assert t["wall_seconds"] == pytest.approx(2.3)
    # no filter: both traces come back
    assert set(rescale_timeline(spans)) == {tid, "rescale-e000009"}


def test_rescale_phase_vocabulary_is_stable():
    # the bench artifact and the e2e test are written against these names
    assert RESCALE_PHASES == (
        "preempt_drain", "drain", "checkpoint", "replan", "warm_compile",
        "restore", "reshard", "first_step"
    )


def test_rescale_timeline_surfaces_unknown_phases():
    tid = "rescale-e000021"
    spans = [
        Span("drain", 1.0, 1.1, trace_id=tid, component="worker"),
        Span("teleport", 1.1, 1.2, trace_id=tid, component="worker"),
    ]
    t = rescale_timeline(spans)[tid]
    # the stray name is kept in phases AND called out, not dropped
    assert t["phases"]["teleport"]["seconds"] == pytest.approx(0.1)
    assert t["unknown_phases"] == ["teleport"]


# -- JAX's compile events as spans ---------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


class FakeMonitoring:
    """`jax.monitoring`'s three listener lists, fired the way JAX fires them
    around a compile event: a scalar with the start time as it begins, a
    time span as it ends."""

    def __init__(self):
        self.scalar, self.event, self.span = [], [], []

    def register_scalar_listener(self, f):
        self.scalar.append(f)

    def register_event_listener(self, f):
        self.event.append(f)

    def register_event_time_span_listener(self, f):
        self.span.append(f)

    def unregister_scalar_listener(self, f):
        self.scalar.remove(f)

    def unregister_event_listener(self, f):
        self.event.remove(f)

    def unregister_event_time_span_listener(self, f):
        self.span.remove(f)

    @contextlib.contextmanager
    def compiling(self, event, fun):
        start = time.time()
        for f in list(self.scalar):
            f(event, start, fun_name=fun)
        yield
        for f in list(self.span):
            f(event, start, time.time(), fun_name=fun)

    def answer(self, event):
        for f in list(self.event):
            f(event)

    def listeners(self):
        return len(self.scalar), len(self.event), len(self.span)


@pytest.fixture
def fake_jax(monkeypatch):
    fake = FakeMonitoring()
    monkeypatch.setitem(sys.modules, "jax", SimpleNamespace(monitoring=fake))
    return fake


def test_tracer_record_takes_a_parent_and_current_is_the_innermost_span():
    tr = Tracer(annotation=contextlib.nullcontext)
    assert tr.current() is None
    with tr.span("step_dispatch") as outer:
        assert tr.current() is outer
        with tr.span("inner") as inner:
            assert tr.current() is inner
        got = tr.record("xla_compile", 1.0, 2.0, parent=tr.current(),
                        fun="jit(_step)")
    assert tr.current() is None
    assert got.parent is outer and got.attrs == {"fun": "jit(_step)"}
    assert got.to_dict()["parent"] == "step_dispatch"
    assert tr.record("drain", 1.0, 2.0).parent is None


def test_compile_spans_record_outermost_compiles_under_the_open_span(
        fake_jax):
    tr = Tracer(annotation=contextlib.nullcontext)
    with tr.compile_spans():
        assert fake_jax.listeners() == (1, 1, 1)
        with fake_jax.compiling(TRACE, "outside"):  # no span open: not ours
            pass
        with tr.span("step_dispatch", step=1):
            with fake_jax.compiling(TRACE, "_step"):
                with fake_jax.compiling(TRACE, "inner"):  # a jit in a trace
                    pass
                with fake_jax.compiling(COMPILE, "jit(eager)"):
                    fake_jax.answer(HIT)
            with fake_jax.compiling(LOWER, "jit(_step)"):
                pass
            with fake_jax.compiling(COMPILE, "jit(_step)"):
                fake_jax.answer(MISS)
            with fake_jax.compiling(COMPILE, "jit(_step)"):
                fake_jax.answer("/jax/compilation_cache/tasks_using_cache")
        def compile_elsewhere():  # a thread with no span of this tracer
            with fake_jax.compiling(COMPILE, "jit(reference)"):
                pass

        elsewhere = threading.Thread(target=compile_elsewhere)
        elsewhere.start()
        elsewhere.join(10)
        assert not elsewhere.is_alive()
        with tr.span("place"):
            with fake_jax.compiling(COMPILE, "jit(put)"):
                fake_jax.answer(HIT)
    assert fake_jax.listeners() == (0, 0, 0)
    assert [(s.name, s.parent and s.parent.name, s.attrs)
            for s in tr.spans] == [
        ("jax_trace", "step_dispatch", {"fun": "_step"}),
        ("jax_lower", "step_dispatch", {"fun": "jit(_step)"}),
        ("xla_compile", "step_dispatch", {"fun": "jit(_step)",
                                          "cache": "miss"}),
        ("xla_compile", "step_dispatch", {"fun": "jit(_step)",
                                          "cache": "off"}),
        ("step_dispatch", None, {"step": 1}),
        ("xla_compile", "place", {"fun": "jit(put)", "cache": "hit"}),
        ("place", None, {}),
    ]
    compiles = [s for s in tr.spans if s.parent is not None]
    assert all(a.end <= b.start for a, b in zip(compiles, compiles[1:]))


def test_compile_spans_register_once_a_tracer_and_unregister_on_raise(
        fake_jax):
    a, b = Tracer(), Tracer()
    with pytest.raises(KeyError):
        with a.compile_spans():
            with a.compile_spans():  # two workers on one tracer
                assert fake_jax.listeners() == (1, 1, 1)
                with b.compile_spans():
                    assert fake_jax.listeners() == (2, 2, 2)
            assert fake_jax.listeners() == (1, 1, 1)
            raise KeyError("boom")
    assert fake_jax.listeners() == (0, 0, 0)


def test_compile_spans_without_jax_do_nothing_and_import_nothing():
    code = ("import sys, edl_tpu.obs.tracing as t\n"
            "tr = t.Tracer()\n"
            "with tr.compile_spans():\n"
            "    with tr.span('init_state'):\n"
            "        pass\n"
            "assert [s.name for s in tr.spans] == ['init_state']\n"
            "assert 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# -- HTTP endpoints ------------------------------------------------------------


def test_metrics_server_endpoints():
    reg = MetricsRegistry()
    reg.counter("srv_total").inc(5)
    tr = Tracer(component="worker")
    tr.record("drain", 1.0, 2.0, trace_id="rescale-e000001")

    with MetricsServer(registry=reg, tracer=tr, host="127.0.0.1", port=0,
                       health=lambda: {"epoch": 3}) as srv:
        text = scrape_metrics(srv.url)
        fams = parse_prometheus(text)
        assert fams["srv_total"]["samples"]["srv_total"] == 5.0

        with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as r:
            payload = json.loads(r.read().decode())
        assert payload["ok"] is True and payload["epoch"] == 3

        with urllib.request.urlopen(srv.url + "/spans", timeout=5) as r:
            lines = [json.loads(l) for l in r.read().decode().splitlines()]
        assert lines[0]["name"] == "drain"
        assert lines[0]["trace_id"] == "rescale-e000001"

        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/nope", timeout=5)


def test_metrics_server_healthz_survives_broken_health_callable():
    reg = MetricsRegistry()

    def bad_health():
        raise RuntimeError("probe me anyway")

    with MetricsServer(registry=reg, host="127.0.0.1", health=bad_health) as srv:
        with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as r:
            payload = json.loads(r.read().decode())
        assert payload["ok"] is False
        assert "RuntimeError" in payload["error"]


def test_concurrent_scrapes_do_not_corrupt():
    reg = MetricsRegistry()
    c = reg.counter("conc_total")
    errors = []

    with MetricsServer(registry=reg, host="127.0.0.1") as srv:

        def hammer():
            try:
                for _ in range(10):
                    c.inc()
                    parse_prometheus(scrape_metrics(srv.url))
            except Exception as e:  # noqa: BLE001 - collected for the assert
                errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    assert not errors
    assert c.value() == 40.0


# -- coordinator status bridge -------------------------------------------------


class _FakeStatusClient:
    """CoordinatorClient surface: call('status') with a scripted reply."""

    def __init__(self, reply):
        self.reply = reply

    def call(self, op, timeout=None):
        assert op == "status"
        if isinstance(self.reply, Exception):
            raise self.reply
        return self.reply


def test_bridge_publishes_status_and_per_worker_leases():
    reg = MetricsRegistry()
    client = _FakeStatusClient({
        "ok": True, "epoch": 4, "queued": 2, "leased": 3, "done": 7,
        "ops": 100, "uptime_seconds": 12.5,
        "lease_holders": ["trainer-0=2", "trainer-1=1", "garbage"],
    })
    bridge = CoordinatorStatusBridge(client, registry=reg).register()
    fams = parse_prometheus(reg.render_prometheus())
    assert fams["edl_coordinator_up"]["samples"]["edl_coordinator_up"] == 1.0
    assert fams["edl_coordinator_epoch"]["samples"]["edl_coordinator_epoch"] == 4.0
    assert fams["edl_coordinator_uptime_seconds"]["samples"][
        "edl_coordinator_uptime_seconds"] == 12.5
    leases = fams["edl_coordinator_worker_leases"]["samples"]
    assert leases['edl_coordinator_worker_leases{worker="trainer-0"}'] == 2.0
    assert leases['edl_coordinator_worker_leases{worker="trainer-1"}'] == 1.0

    # a worker whose leases all completed is zeroed, not left dangling stale
    client.reply = dict(client.reply, lease_holders=["trainer-1=4"])
    leases = parse_prometheus(reg.render_prometheus())[
        "edl_coordinator_worker_leases"]["samples"]
    assert leases['edl_coordinator_worker_leases{worker="trainer-0"}'] == 0.0
    assert leases['edl_coordinator_worker_leases{worker="trainer-1"}'] == 4.0
    bridge.unregister()


def test_bridge_unreachable_coordinator_reads_up_zero():
    reg = MetricsRegistry()
    client = _FakeStatusClient({
        "ok": True, "epoch": 9, "lease_holders": [],
    })
    bridge = CoordinatorStatusBridge(client, registry=reg).register()
    reg.render_prometheus()
    client.reply = OSError("connection refused")
    fams = parse_prometheus(reg.render_prometheus())
    assert fams["edl_coordinator_up"]["samples"]["edl_coordinator_up"] == 0.0
    # last-known values stay in place; staleness is signalled via `up`
    assert fams["edl_coordinator_epoch"]["samples"]["edl_coordinator_epoch"] == 9.0
    bridge.unregister()


# -- structured logging --------------------------------------------------------


def test_json_log_formatter_fields_and_extras():
    fmt = JsonLogFormatter()
    logger = logging.Logger("edl_tpu.test.obs")
    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    handler.setFormatter(fmt)
    logger.addHandler(handler)

    logger.info("hello %s", "world",
                extra={"epoch": 3, "mesh": (2, 4), "dev": object()})
    try:
        raise ValueError("boom")
    except ValueError:
        logger.exception("failed")

    lines = [json.loads(l) for l in stream.getvalue().splitlines()]
    assert lines[0]["msg"] == "hello world"
    assert lines[0]["level"] == "info"
    assert lines[0]["logger"] == "edl_tpu.test.obs"
    assert lines[0]["epoch"] == 3
    assert lines[0]["mesh"] == [2, 4]  # tuples serialize as JSON arrays
    assert lines[0]["dev"].startswith("<object")  # non-JSON extras -> repr
    assert math.isfinite(lines[0]["ts"])
    assert lines[1]["level"] == "error"
    assert "ValueError: boom" in lines[1]["exc"]


def test_configure_logging_json_stream():
    root = logging.getLogger()
    saved_handlers, saved_level = list(root.handlers), root.level
    stream = io.StringIO()
    try:
        configure_logging(level="warning", fmt="json", stream=stream)
        logging.getLogger("edl_tpu.obs.test").warning("structured %d", 7)
        rec = json.loads(stream.getvalue().strip())
        assert rec["msg"] == "structured 7"
        assert rec["logger"] == "edl_tpu.obs.test"
    finally:
        for h in list(root.handlers):
            root.removeHandler(h)
        for h in saved_handlers:
            root.addHandler(h)
        root.setLevel(saved_level)


# -- collector: coordinator-health block (supervised control plane) ------------


class _FakeSupervisor:
    """CoordinatorSupervisor surface: summary() -> Dict[str, float]."""

    def __init__(self):
        self.restarts = 0.0
        self.downtime = 0.0

    def summary(self):
        return {
            "restarts": self.restarts,
            "downtime_seconds": self.downtime,
            "last_restart_rc": -6.0 if self.restarts else -1.0,
        }


def _tiny_cluster():
    return FakeCluster([
        NodeInfo(name="h0", allocatable=ResourceList.make(
            {"cpu": 8, "memory": "32Gi", "tpu": 8})),
    ])


def test_collector_propagates_supervisor_health_and_roundtrips_jsonl():
    sup = _FakeSupervisor()
    sink = io.StringIO()
    collector = Collector(JobStore(), _tiny_cluster(), period_seconds=10.0,
                          sink=sink, supervisor=sup)
    s0 = collector.sample()
    assert s0.coordinator["restarts"] == 0.0
    assert s0.coordinator["downtime_seconds"] == 0.0

    # the coordinator dies and the supervisor resurrects it twice
    sup.restarts, sup.downtime = 2.0, 1.25
    s1 = collector.sample()
    assert s1.coordinator["restarts"] == 2.0
    assert s1.coordinator["downtime_seconds"] == 1.25
    assert s1.coordinator["last_restart_rc"] == -6.0

    # JSONL round-trip: the health block survives serialization intact
    lines = [json.loads(l) for l in sink.getvalue().splitlines()]
    assert len(lines) == 2
    assert lines[0]["coordinator"]["restarts"] == 0.0
    assert lines[1]["coordinator"] == {
        "restarts": 2.0, "downtime_seconds": 1.25, "last_restart_rc": -6.0,
    }


def test_collector_without_supervisor_emits_empty_health_block():
    sink = io.StringIO()
    collector = Collector(JobStore(), _tiny_cluster(), sink=sink)
    s = collector.sample()
    assert s.coordinator == {}
    assert json.loads(sink.getvalue().strip())["coordinator"] == {}


# -- `edl-tpu status` subcommand -----------------------------------------------


def test_cli_status_against_live_coordinator(capsys):
    from edl_tpu.cli import main
    from edl_tpu.coordinator import CoordinatorServer
    from edl_tpu.runtime import shard_names

    with CoordinatorServer() as server:
        w = server.client("trainer-0")
        w.register()
        w.add_tasks(shard_names("cli", 3))
        assert w.acquire_task() is not None

        rc = main(["status", "--port", str(server.port)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "ok" in out
        assert "queued" in out and "leased" in out
        assert "uptime_seconds" in out
        # the per-worker lease table renders the native lease_holders encoding
        assert "per-worker leases:" in out
        assert "trainer-0" in out

        rc = main(["status", "--port", str(server.port), "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["ok"] is True
        assert payload["leased"] == 1
        assert payload["lease_holders"] == ["trainer-0=1"]


def test_cli_status_unreachable_coordinator(capsys):
    from edl_tpu.cli import main

    rc = main(["status", "--port", "1", "--timeout", "0.5"])
    assert rc == 1
    assert "ERROR" in capsys.readouterr().err


def test_cli_status_renders_ft_policy_section(capsys):
    """Workers publish their live policy state to coordinator KV
    (edl/ft_policy/<worker>); `edl-tpu status` reads it back per member."""
    from edl_tpu.cli import main
    from edl_tpu.coordinator import CoordinatorServer

    with CoordinatorServer() as server:
        w = server.client("trainer-0")
        w.register()
        w.kv_put("edl/ft_policy/trainer-0", json.dumps({
            "policy": "adaptive", "mode": "park", "threshold": 4.2,
            "incidents": 3, "storm": False,
        }))

        rc = main(["status", "--port", str(server.port)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fault-tolerance policy:" in out
        assert "policy=adaptive" in out and "mode=park" in out

        rc = main(["status", "--port", str(server.port), "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["ft_policy"]["trainer-0"]["mode"] == "park"
        assert payload["ft_policy"]["trainer-0"]["threshold"] == 4.2


def test_cli_status_renders_lm_serving_section(capsys):
    """LM replicas publish kind="lm" blobs under edl/serving/<member>;
    `edl-tpu status` renders the decode-native numbers (streams, tokens/s,
    KV block pool) instead of the batch tier's queue/bucket line, and
    --json carries the blob through verbatim."""
    from edl_tpu.cli import main
    from edl_tpu.coordinator import CoordinatorServer

    lm_blob = {
        "name": "lm-0", "kind": "lm", "model_step": 100, "version": 3,
        "active_streams": 2, "waiting_streams": 0, "completed": 7,
        "rejected": 1, "evicted": 0, "tokens_generated": 56,
        "tokens_per_s": 12.5, "batch_buckets": [1, 4],
        "seq_buckets": [64, 128],
        "kv": {"n_blocks": 64, "block_tokens": 16, "used_blocks": 9,
               "free_blocks": 55, "peak_blocks_used": 12, "streams": 2,
               "occupancy": 0.1406, "fragmentation": 0.42},
    }
    batch_blob = {
        "name": "serve-0", "kind": "batch", "model_step": 200, "version": 5,
        "queue_depth": 0, "bucket_hits": {"4": 3}, "last_swap_step": 100,
        "completed": 12,
    }
    with CoordinatorServer() as server:
        w = server.client("lm-0")
        w.register()
        w.kv_put("edl/serving/lm-0", json.dumps(lm_blob))
        w2 = server.client("serve-0")
        w2.register()
        w2.kv_put("edl/serving/serve-0", json.dumps(batch_blob))

        rc = main(["status", "--port", str(server.port)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "serving replicas:" in out
        # the LM line renders stream/token/KV state...
        assert "kind=lm" in out
        assert "tokens/s=12.5" in out
        assert "kv_blocks=9/64" in out and "frag=0.42" in out
        assert "streams=2" in out
        # ...while the batch replica keeps its queue/bucket rendering
        assert "queue=0" in out and "buckets=4:3" in out

        rc = main(["status", "--port", str(server.port), "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["serving"]["lm-0"]["kind"] == "lm"
        assert payload["serving"]["lm-0"]["kv"]["free_blocks"] == 55
        assert payload["serving"]["lm-0"]["tokens_generated"] == 56
        assert payload["serving"]["serve-0"]["kind"] == "batch"
