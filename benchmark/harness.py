"""The harness: finds a cell's files by the names in ``BENCHMARK.json``, gates
on the device, runs the cell's driver, reads the per-layer metrics through
their readers, decides ``correct`` and prints the result line.

It is driven by data. A cell names a configuration and a traffic mix; the
configuration names its ``program`` and ``compare`` modules (the dense
decoder's where it names none), the mix its generator and its driver, by
module; a per-layer metric is
``benchmark/metrics/<name>.json`` naming its reader module and arguments; a
cell's limits are ``benchmark/limits/<cell>.json``. Adding any of them is
adding files and entries, never editing one that is there.
"""
from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """Everything the data files say about one cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    limits_path = os.path.join(HERE, "limits", workload + ".json")
    return {
        "bench": bench, "cell": cell,
        "config": load_json(ROOT, cfg_entry["file"]),
        "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
        "limits": load_json(limits_path) if os.path.exists(limits_path)
        else {},
    }


def module_of(config: dict, role: str):
    """The ``program`` or ``compare`` module that a configuration names:
    what builds the system under test, and what judges it against the plain
    reference. A configuration that names none is the dense decoder's."""
    return importlib.import_module(config.get(role, "benchmark." + role))


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def device_gate(chips: int) -> dict:
    """The device as JAX reports it; no accelerator, or fewer chips than the
    cell asks for, ends the run with no result."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"no accelerator: JAX's platform is "
                         f"{info['platform']!r}; the benchmark never runs on "
                         f"a CPU")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    from benchmark import costs

    costs.peaks(info["kind"])        # an unknown device is an error, early
    return info


def memory_peak_bytes(chips: int):
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def read_layer_metrics(loaded: dict, workload: str, bag: dict) -> dict:
    """Each per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in loaded["bench"]["per_layer"]:
        if not applies(metric, workload):
            continue
        spec = load_json(HERE, "metrics", metric["name"] + ".json")
        reader = importlib.import_module(spec["reader"])
        value = getattr(reader, spec["function"])(bag, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def decide(checks: list) -> bool:
    """``checks``: ``(name, value, limit)``; a value that is missing, not a
    number or over its limit fails."""
    ok = bool(checks)
    for _, value, limit in checks:
        if value is None or limit is None or not value <= limit:
            ok = False
    return ok


def run_cell(loaded: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_process: float, device: dict,
             control: str | None = None) -> dict:
    """Everything after the look for a chip: drive, read, compare."""
    driver = importlib.import_module(loaded["traffic"]["driver"])
    ctx = {"workload": workload, "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "t_process": t_process, "device": device,
           "chips": loaded["cell"]["chips"], "config": loaded["config"],
           "traffic": loaded["traffic"], "limits": loaded["limits"],
           "workdir": os.path.join(ROOT, ".bench_tmp"), "log": log,
           "control": control}
    bag = driver.run(ctx)
    bench = loaded["bench"]
    if trace:
        metrics = read_layer_metrics(loaded, workload, bag)
    else:
        metrics = {}
        for metric in bench["end_to_end"]:
            if applies(metric, workload):
                metrics[metric["name"]] = {
                    "value": float(bag["end_to_end"][metric["name"]]),
                    "unit": metric["unit"]}
    checks = bag["checks"]
    dev = dict(device)
    dev["memory_peak_bytes"] = bag["memory_peak_bytes"]
    if trace and bag.get("trace"):
        dev["busy_s"] = bag["trace"]["busy_s"]
        dev["window_s"] = bag["trace"]["window_s"]
    result = {"correct": decide(checks), "attempted": bag["attempted"],
              "failed": bag["failed"], "metrics": metrics, "device": dev}
    if trace and bag.get("trace"):
        result["breakdown"] = bag["trace"]["breakdown"]
    if trace:
        result["end_to_end_traced"] = bag["end_to_end"]
    result["notes"] = bag.get("notes", {})
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in checks}
    return result


def main(workload, seed, seconds, trace, t_process) -> int:
    loaded = load_cell(workload)
    device = device_gate(loaded["cell"]["chips"])
    log(f"device {device}; cell {workload} seed {seed} seconds {seconds} "
        f"trace {int(trace)}")
    result = run_cell(loaded, workload, seed, seconds, trace, t_process,
                      device)
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']}) on "
            f"{device['kind']}")
    log(f"correct {result['correct']} on {device['kind']} x "
        f"{result['device']['count']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
