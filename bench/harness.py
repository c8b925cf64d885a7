"""Finds a cell's files by name, checks the device, assembles the result.

Nothing here names a configuration, a traffic mix or a metric: a cell is
``BENCHMARK.json``'s workload entry plus the files its names point at.

- ``configs/<config>.json`` (the entry's ``file``): the registry id and
  the model sizes as run, with the source, ``reduced`` and ``assumed``;
  its ``costs`` names ``costs/<costs>.py``, the model's operation count.
- ``traffic/<mix>.json``: ``kind`` picks ``drivers/<kind>.py``; the rest
  is that driver's parameters (lengths, arrivals, engine settings).
- ``limits/<workload>.json``: the limit of each number ``correct`` compares.
- ``layer_metrics/<metric>.py``: one ``read(ctx) -> float | None`` per
  per-layer metric, run on the traced run's context.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import os
import shutil
import sys
from typing import Any, Callable, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: fixed path inside the checkout: the path is part of JAX's cache key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: profiler traces of ``--trace 1`` runs, removed once reduced
TRACE_DIR = os.path.join(ROOT, ".bench_traces")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class SpecError(ValueError):
    """A name in BENCHMARK.json does not resolve to its file."""


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def _find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SpecError(f"BENCHMARK.json has no {what} named {name!r}")


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload entry with everything its names resolve to."""
    name: str
    chips: int
    config: dict              # configs/<config>.json
    traffic: dict             # traffic/<mix>.json
    limits: dict              # limits/<workload>.json: check name -> limit
    end_to_end: list          # BENCHMARK.json metric entries it reports
    per_layer: list
    driver: Any               # drivers/<kind>.py
    readers: dict             # per-layer metric name -> read(ctx)
    costs: Any                # costs/<config['costs']>.py


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(spec: dict, workload: str, root: str = ROOT) -> Cell:
    """Everything ``workload`` needs, found by the names in ``spec``."""
    bench = os.path.join(root, "bench")
    w = _find(spec["workloads"], workload, "workload")
    conf = _find(spec["configs"], w["config"], "config")
    config = read_json(os.path.join(root, conf["file"]))
    traffic = read_json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    limits = read_json(os.path.join(bench, "limits", workload + ".json"))
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    driver = load_module(os.path.join(bench, "drivers", traffic["kind"] + ".py"),
                         f"bench_driver_{traffic['kind']}")
    readers = {m["name"]: load_module(
        os.path.join(bench, "layer_metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")).read for m in layer}
    costs = load_module(os.path.join(bench, "costs", config["costs"] + ".py"),
                        "bench_costs_" + config["costs"])
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                layer, driver, readers, costs)


def program_config(config: dict):
    """The program's ModelConfig for a config file: its registry entry
    with every field the file's ``model`` entry gives set to that value."""
    from repro.configs import registry
    base = registry.get_config(config["registry"])
    unknown = set(config["model"]) - {f.name for f in dataclasses.fields(base)}
    if unknown:
        raise SpecError(f"{config['name']}: unknown model keys {sorted(unknown)}")
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in config["model"].items()}
    return dataclasses.replace(base, **fields)


def reference_module(config: dict):
    """``bench/reference/<config['reference']>.py``."""
    return importlib.import_module("bench.reference." + config["reference"])


def leaf_norms(tree) -> dict:
    """{'a/b/c': float32 L2 norm} of every array leaf, computed on the
    device and fetched once."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {n: float(v) for n, v in zip(names, jax.device_get(norms))}


def peaks_for(device_kind: str, root: str = ROOT) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = read_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def check_devices(chips: int):
    """The accelerator devices a cell of ``chips`` chips runs on."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip(f"JAX found no accelerator, only {len(devices)} cpu "
                     f"device(s)")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def prepare_process() -> None:
    """Before JAX starts: keep the runtime's logs out of fixed paths."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache``, whatever the
    environment says, so that two checkouts never share one."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CACHE_DIR


@dataclasses.dataclass
class Run:
    """What a driver is given for one run of one cell."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # process start, perf_counter
    devices: list
    peaks: dict
    log: Callable[[str], None]
    trace_dir: str = TRACE_DIR
    #: planted faults, for the tests that prove ``correct`` can fail
    faults: frozenset = frozenset()
    #: also read the float8 control's numbers (``bench/readings.py``)
    control: bool = False


@dataclasses.dataclass
class Outcome:
    """What a driver returns: host-clock metrics, checks, trace context."""
    setup_s: float
    e2e: dict                           # end-to-end metric -> value
    attempted: int
    failed: int
    checks: dict                        # check name -> measured number
    memory_peak_bytes: Optional[int]
    layer: dict = dataclasses.field(default_factory=dict)
    #: the control's numbers, by check name, when ``Run.control``
    control: dict = dataclasses.field(default_factory=dict)


@functools.cache
def _compile_counter() -> list:
    import jax
    count = [0]

    def on_event(event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return count


def compiles() -> int:
    """XLA executables this process has compiled or loaded from the
    persistent cache so far; none should be built inside a window."""
    return _compile_counter()[0]


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def judge(checks: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number compared beside its limit.  A number
    that is missing, not finite or above its limit makes the run
    incorrect.  Numbers a driver reads that have no limit are not
    compared (their readings gave them no upper end)."""
    shown = {}
    ok = True
    for name, limit in limits.items():
        value = checks.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok &= good
        shown[name] = {"value": value, "limit": limit}
    return ok, shown


def result_line(cell: Cell, run: Run, out: Outcome, devices) -> dict:
    """The last line of standard output."""
    correct, shown = judge(out.checks, cell.limits)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    breakdown = None
    if run.trace:
        ctx = out.layer
        device["busy_s"] = ctx["trace"].busy_s
        device["window_s"] = ctx["trace"].window_s
        for name, read in cell.readers.items():
            value = read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        breakdown = ctx["trace"].breakdown()
    else:
        values = dict(out.e2e, setup_s=out.setup_s)
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # the numbers compared, each beside its limit, come last
    line["checks"] = shown
    return line


def print_result(line: dict, checks: dict) -> None:
    for name in checks.keys() - line["checks"].keys():
        print(f"reading {name}: {checks[name]!r} (not compared)",
              file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def clear_traces(path: str = TRACE_DIR) -> None:
    shutil.rmtree(path, ignore_errors=True)
