#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run: it finds the cell in ``BENCHMARK.json``, the cell's
configuration, traffic, runner and per-layer metrics as files of this
directory **by name**, sets the system up from ``--seed``, warms up, measures
for ``--seconds``, checks the outputs, and prints the contract's JSON object as
the last line of standard output. ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` traces a short steady part of the window with
``jax.profiler`` and reports the cell's per-layer metrics and a breakdown.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. See README.md beside this file.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us stamp it

import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
from typing import Dict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def log(msg: str) -> None:
    print(msg, flush=True)


# -- the data: BENCHMARK.json and the files it names ----------------------------


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(bench_dir: str, kind: str, name: str):
    """``<bench_dir>/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def validate(bench: dict, bench_dir: str) -> None:
    """What a new entry or file must satisfy for the harness to run it:
    names, units, files found by name, and every ``moves`` an end-to-end
    metric that each cell of the per-layer metric reports. The limits on
    sizes and bounds are the driver's to check."""
    def need(ok, why):
        if not ok:
            raise ValueError(f"BENCHMARK.json: {why}")

    e2e = {m["name"]: m for m in bench["end_to_end"]}
    need("setup_s" in e2e, "no setup_s among end_to_end")
    for m in bench["end_to_end"] + bench["per_layer"]:
        need(NAME.match(m["name"]), f"bad metric name {m['name']!r}")
        need(UNIT.match(m["unit"]), f"bad unit {m['unit']!r} of {m['name']}")
        need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        need(m["source"] in SOURCES, f"{m['name']}: source {m['source']!r}")
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for c in bench["configs"]:
        need(NAME.match(c["name"]), f"bad config name {c['name']!r}")
        need(os.path.isfile(os.path.join(bench_dir, "..", c["file"])),
             f"config file {c['file']} not found")
    for w in bench["workloads"]:
        need(NAME.match(w["name"]) and NAME.match(w["traffic"]),
             f"bad name in cell {w['name']!r}")
        need(w["config"] in configs, f"{w['name']}: config {w['config']!r}")
        need(w["chips"] in (1, 4), f"{w['name']}: chips")
        for kind, name in (("workloads", w["name"]), ("traffic", w["traffic"])):
            need(os.path.isfile(os.path.join(bench_dir, kind, f"{name}.json")),
                 f"{kind}/{name}.json not found")
        mine = [m["name"] for m in bench["end_to_end"] if reports(m, w["name"])]
        need("setup_s" in mine and len(mine) >= 2,
             f"{w['name']} reports no end-to-end metric besides setup_s")
        need(any(reports(m, w["name"]) for m in bench["per_layer"]),
             f"{w['name']} reports no per-layer metric")
    for m in bench["per_layer"]:
        need(m["moves"] in e2e, f"{m['name']} moves unknown {m['moves']!r}")
        need(os.path.isfile(os.path.join(
            bench_dir, "layer_metrics", f"{m['name']}.json")),
            f"layer_metrics/{m['name']}.json not found")
        for cell in m.get("workloads", cells):
            need(cell in cells, f"{m['name']}: unknown cell {cell!r}")
            need(reports(e2e[m["moves"]], cell),
                 f"{m['name']} moves {m['moves']}, which {cell} does not report")


def model_kwargs(config: dict) -> dict:
    """The configuration's published sizes under the program's names."""
    return {ours: config[theirs] for theirs, ours in config["maps_to"].items()}


# -- the device -----------------------------------------------------------------


def require_devices(chips: int) -> list:
    """The cell's chips, or no run: there is no fallback to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found platform "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s); JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def use_compile_cache() -> Dict[str, int]:
    """JAX's persistent cache by the repo's one rule (the directory named by
    ``JAX_COMPILATION_CACHE_DIR``, else the checkout's fixed ``.jax_cache/``),
    and a count of compile requests: hits and misses of that cache."""
    import jax
    from edl_tpu.launcher.launch import jax_cache_dir

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", jax_cache_dir())
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    return counts


def device_report(devices, memory_peak_bytes: int) -> dict:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(memory_peak_bytes)}


# -- one run ----------------------------------------------------------------------


def run_cell(bench: dict, bench_dir: str, name: str, seed: int, seconds: float,
             trace: bool, devices: list) -> dict:
    """Run one cell on ``devices`` and return the result object."""
    import trace_reduce
    from cell import Cell, ReadContext

    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = load_json(bench_dir, "..", config_entry["file"])
    workload = load_json(bench_dir, "workloads", f"{name}.json")
    workdir = os.path.join(bench_dir, ".work", name)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    cell = Cell(
        name=name, chips=entry["chips"], config=config,
        model_kwargs=model_kwargs(config),
        traffic=load_json(bench_dir, "traffic", f"{entry['traffic']}.json"),
        workload=workload, seed=seed, seconds=seconds, trace=trace,
        devices=devices, workdir=workdir,
        trace_dir=os.path.join(workdir, "trace"), t0=T0, log=log)
    compiles = use_compile_cache()
    try:
        runner = load_module(bench_dir, "runners", workload["runner"])
        out = runner.run(cell, compiles)  # cell.Outcome
        device = device_report(devices, out.memory_peak_bytes)
        log(f"compile cache: {json.dumps(compiles)}")
        if not trace:
            mine = [m for m in bench["end_to_end"] if reports(m, name)]
            missing = [m["name"] for m in mine
                       if m["name"] not in out.end_to_end]
            if missing:
                raise RuntimeError(f"runner reported no {missing}")
            metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                                   "unit": m["unit"]} for m in mine}
            return {"correct": out.correct, "attempted": out.attempted,
                    "failed": out.failed, "metrics": metrics, "device": device}

        reduced = None
        xplane = trace_reduce.find_xplane(cell.trace_dir)
        if xplane is not None:
            reduced = trace_reduce.load(xplane)
            log(f"trace planes and lines: {json.dumps(reduced.seen)}")
            log(f"trace custom calls: {json.dumps(reduced.custom_calls)}")
            top = sorted(trace_reduce.self_seconds_by_device(reduced).items(),
                         key=lambda kv: -kv[1])[:30]
            log(f"trace operations by self time: {json.dumps(top)}")
        if reduced is None or not reduced.busy_s:
            raise RuntimeError("the traced run saw no operation on the device")
        ctx = ReadContext(spans=out.spans, values=out.values, trace=reduced,
                          device=device, chips=entry["chips"],
                          model_kwargs=cell.model_kwargs)
        metrics = {}
        for m in bench["per_layer"]:
            if not reports(m, name):
                continue
            how = load_json(bench_dir, "layer_metrics", f"{m['name']}.json")
            reader = load_module(bench_dir, "readers", how["reader"])
            value = reader.read(ctx, **how.get("args", {}))
            if value is not None:  # nothing to read: left out of the line
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        return {"correct": out.correct, "attempted": out.attempted,
                "failed": out.failed, "metrics": metrics, "device": device,
                "breakdown": reduced.breakdown()}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    repo = os.path.dirname(BENCH_DIR)
    for path in (BENCH_DIR, repo):
        if path not in sys.path:
            sys.path.insert(0, path)
    bench = load_json(repo, "BENCHMARK.json")
    validate(bench, BENCH_DIR)
    chips = next((w["chips"] for w in bench["workloads"]
                  if w["name"] == args.workload), 1)
    devices = require_devices(chips)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    result = run_cell(bench, BENCH_DIR, args.workload, args.seed, seconds,
                      bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
