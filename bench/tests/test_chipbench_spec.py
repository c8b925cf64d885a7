"""CPU checks of the chip benchmark's data and arithmetic: every name in
``BENCHMARK.json`` resolves to its files, the cost functions match hand
counts, the peaks table refuses an unknown device, the trace reduction
adds up on a recorded trace, a cell can be added from files alone, and
the command refuses to run without a chip."""
from __future__ import annotations

import filecmp
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from bench import harness, readers, trace_reduce  # noqa: E402
from bench.costs import dense_lm, paged_attention, ssd_lm  # noqa: E402

ROOT = tiny.ROOT
SPEC = harness.load_spec(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_tpu.xplane.pb")


def _reported(workload: str) -> set:
    return {m["name"] for m in SPEC["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}


def test_spec_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in SPEC[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["layer"].strip()
        for w in m["workloads"]:
            assert m["moves"] in _reported(w)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(workload):
    cell = harness.resolve(SPEC, workload)
    reported = _reported(workload)
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer and set(cell.readers) == {
        m["name"] for m in cell.per_layer}
    assert callable(cell.driver.run)
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    cfg = harness.program_config(cell.config)
    for key, value in cell.config["model"].items():
        got = getattr(cfg, key)
        assert (list(got) if isinstance(got, tuple) else got) == value
    harness.reference_module(cell.config)


def test_every_config_file_is_named_once():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        conf = harness.read_json(os.path.join(ROOT, c["file"]))
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "bench", "configs"))))
def test_every_config_file_builds_the_program_config(name):
    conf = harness.read_json(os.path.join(ROOT, "bench", "configs",
                                          name + ".json"))
    assert conf["name"] == name and conf["source"].startswith("https://")
    cfg = harness.program_config(conf)
    for key, value in conf["model"].items():
        got = getattr(cfg, key)
        assert (list(got) if isinstance(got, tuple) else got) == value
    harness.reference_module(conf)


def test_ssd_training_flops_match_a_hand_count():
    m = harness.read_json(os.path.join(
        ROOT, "bench/configs/mamba2-130m.json"))["model"]
    d, di, n, heads, q, layers, vocab = 768, 1536, 128, 24, 256, 24, 50280
    proj = d * 2 * di + d * 2 * n + d * heads + di * d
    causal = (q + 1) / 2                 # keys per query inside a chunk
    ssd = 2 * (causal * n + causal * di + 2 * di * n)
    forward = layers * (2 * proj + ssd) + 2 * vocab * d
    assert ssd_lm.projection_params(m) == 3_753_984
    assert ssd_lm.train_flops_per_token(m, 2048) == pytest.approx(
        3 * forward, rel=1e-12)
    assert 0.85e9 < ssd_lm.train_flops_per_token(m, 2048) < 0.87e9


def test_dense_tick_flops_count_valid_tokens_only():
    m = harness.read_json(os.path.join(
        ROOT, "bench/configs/starcoder2-3b.json"))["model"]
    start, valid = [0, 100, 5], [32, 1, 0]
    linear = 3072 * 24 * 128 * 2 + 3072 * 2 * 128 * 2 + 2 * 3072 * 12288
    ctx = (32 * 33 // 2) + (100 + 1)
    attn = 4 * 24 * 128 * ctx * 30
    assert dense_lm.linear_params(m) == linear
    assert dense_lm.attention_flops(m, start, valid) == attn
    assert dense_lm.tick_flops(m, start, valid) == (
        2 * 30 * linear * 33 + attn + 2 * 3072 * 49152 * 2)


def test_paged_kernel_bytes_for_a_fixed_plan():
    m = harness.read_json(os.path.join(
        ROOT, "bench/configs/starcoder2-3b.json"))["model"]
    start, valid = [0, 100, 5], [32, 1, 0]
    pages = 2 + 7                         # ceil(32/16) + ceil(101/16)
    kv = pages * 16 * 128 * 2 * 2 * 2     # tokens x D x bf16 x (K, V) x heads
    qo = 2 * 3 * 32 * 24 * 128 * 2        # q in, out back, every slot's chunk
    assert paged_attention.call_bytes(m, start, valid, page_size=16,
                                      chunk=32) == kv + qo
    ops, nbytes = paged_attention.tick_cost(m, start, valid, page_size=16,
                                            chunk=32)
    assert nbytes == 30 * (kv + qo)
    assert ops == dense_lm.attention_flops(m, start, valid)
    peaks = harness.peaks_for("TPU v5 lite")
    assert paged_attention.roofline_seconds(ops, nbytes, peaks) == max(
        ops / 197e12, nbytes / 819e9)


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")


def test_trace_reduction_adds_up_on_a_recorded_trace():
    tr = trace_reduce.load(TRACE)
    assert tr.n_devices == 1
    assert 0 < tr.busy_s < tr.window_s
    idle = sum(s for _, s in tr.gaps())
    assert idle + tr.busy_s == pytest.approx(tr.window_s, abs=1e-9)
    ops = tr.op_seconds()
    assert sum(ops.values()) <= tr.busy_s + 1e-9
    assert ops["custom-call:step"] == pytest.approx(tr.kernel_seconds())
    assert tr.kernel_seconds() > 0
    out = tr.breakdown()
    assert 0 < len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][0] == "custom-call:step"
    assert all(label != trace_reduce.WINDOW for label, _ in out["idle_gaps"])


def test_trace_reduction_helpers():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    own = trace_reduce._self_times([("loop", 0, 10), ("a", 1, 3),
                                    ("b", 4, 9), ("c", 5, 6)])
    assert {n: o for n, _, _, o in own} == {"loop": 3, "a": 2, "b": 4, "c": 1}
    assert trace_reduce.op_key("%fusion.12 = bf16[8] fusion(%p)") == "fusion"
    kernel = "%closed_call.3 = bf16[8] custom-call(%a), " \
             "custom_call_target=\"tpu_custom_call\""
    assert trace_reduce.op_key(kernel) == "custom-call:closed_call"
    assert trace_reduce.is_kernel(kernel)


def test_readers_find_nothing_without_data():
    for name in ("train_step_mfu", "serve_step_mfu", "idle_share",
                 "paged_attention_roofline", "host_ms_per_tick",
                 "queue_wait_ms_p90"):
        assert getattr(readers, name)({}) is None


def test_a_cell_is_added_from_files_alone(tmp_path):
    root = tiny.make_root(str(tmp_path))
    ours = os.path.join(ROOT, "bench")
    for dirpath, _, files in os.walk(ours):
        if "__pycache__" in dirpath or os.sep + "tests" in dirpath:
            continue
        for f in files:
            path = os.path.join(dirpath, f)
            copy = os.path.join(root, os.path.relpath(path, ROOT))
            assert filecmp.cmp(path, copy, shallow=False), path
    spec = harness.load_spec(root)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        old = {e["name"]: e for e in SPEC[key]}
        for e in spec[key]:
            if e["name"] in old:
                kept = {k: v for k, v in e.items() if k != "workloads"}
                assert kept == {k: v for k, v in old[e["name"]].items()
                                if k != "workloads"}
                assert set(old[e["name"]].get("workloads", [])) <= set(
                    e.get("workloads", []))
    for name, _, _ in tiny.TINY_WORKLOADS:
        cell = harness.resolve(spec, name, root=root)
        assert cell.readers and cell.limits


def test_the_command_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", SPEC["workloads"][0]["name"], "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_judge_needs_every_number_under_its_limit():
    ok, shown = harness.judge({"a": 0.1, "b": 0.2}, {"a": 0.5, "b": 0.5})
    assert ok and shown["a"] == {"value": 0.1, "limit": 0.5}
    assert not harness.judge({"a": 0.6, "b": 0.2}, {"a": 0.5, "b": 0.5})[0]
    assert not harness.judge({"a": math.nan, "b": 0.2},
                             {"a": 0.5, "b": 0.5})[0]
    assert not harness.judge({"a": 0.1}, {"a": 0.5, "b": 0.5})[0]
    ok, shown = harness.judge({"a": 0.1, "c": 9.0}, {"a": 0.5})
    assert ok and set(shown) == {"a"}
    line = json.dumps(shown)
    assert "limit" in line


CHAT = "starcoder2-3b.chat_open"
CHAT_READERS = {"serve_step.mfu.open", "paged_attention_roofline.open",
                "engine.host_ms_per_tick.open", "scheduler.queue_wait_ms_p90",
                "device.idle_share.open", "serve_step.attention_ms.open",
                "serve_step.mlp_ms.open", "serve_step.layers_ms.open",
                "serve_step.unscoped_ms.open"}


def test_the_chat_cell_resolves_from_the_spec_alone():
    cell = harness.resolve(SPEC, CHAT)
    bench = os.path.join(ROOT, "bench")
    assert cell.chips == 1 and cell.config["name"] == "starcoder2-3b"
    assert cell.driver.__file__ == os.path.join(bench, "drivers",
                                                "serve_open.py")
    mix = cell.traffic
    assert mix["kind"] == "serve_open"
    assert isinstance(mix["rate_per_s"], float) and mix["rate_per_s"] > 0
    assert mix["engine"] == {"n_slots": 32, "page_size": 16,
                             "chunk_size": 32, "max_seq": 4096,
                             "kv_dtype": "bf16", "prefix_cache": False,
                             "pages_per_block": 8}
    assert mix["prompt_tokens"] == {"median": 1020, "sigma": 0.5,
                                    "min": 16, "max": 3072}
    assert mix["new_tokens"] == {"median": 129, "sigma": 0.99,
                                 "min": 1, "max": 1024}
    assert mix["prompt_tokens"]["max"] + mix["new_tokens"]["max"] <= \
        mix["engine"]["max_seq"]
    assert set(cell.limits) == {"logit_gap"}
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert set(cell.readers) == CHAT_READERS
    for name in CHAT_READERS:
        assert os.path.isfile(os.path.join(bench, "layer_metrics",
                                           name + ".py"))
    assert cell.costs.__file__ == os.path.join(bench, "costs", "dense_lm.py")


@pytest.mark.parametrize("config, module", [("mamba2-130m", "ssd_lm"),
                                            ("starcoder2-3b", "dense_lm")])
def test_every_config_names_its_operation_count(config, module):
    conf = harness.read_json(os.path.join(ROOT, "bench", "configs",
                                          config + ".json"))
    assert conf["costs"] == module
    workload = next(w["name"] for w in SPEC["workloads"]
                    if w["config"] == config)
    cell = harness.resolve(SPEC, workload)
    assert cell.costs.__file__ == os.path.join(ROOT, "bench", "costs",
                                               module + ".py")


def test_the_training_count_is_the_ssd_count_as_before():
    from bench.drivers import train
    cell = harness.resolve(SPEC, "mamba2-130m.pretrain_2k")
    m = cell.config["model"]
    assert train.flops_per_token(cell) == ssd_lm.train_flops_per_token(
        m, 2048)


FLAT_COUNT = '''"""A made-up operation count for the tests."""


def train_flops_per_token(m, seq):
    return 1000.0 * seq


def tick_flops(m, start, valid):
    return 7 * int(sum(valid))
'''


def test_a_config_that_names_another_count_gets_it(tmp_path):
    """A tiny cell whose configuration names ``flat_count`` is counted by
    that module, in the training driver and in the serving reader."""
    from bench.drivers import train
    root = tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "bench", "costs", "flat_count.py"),
              "w") as f:
        f.write(FLAT_COUNT)
    for name in tiny.TINY_CONFIGS:
        path = os.path.join(root, "bench", "configs", name + ".json")
        conf = dict(harness.read_json(path), costs="flat_count")
        with open(path, "w") as f:
            json.dump(conf, f)
    spec = harness.load_spec(root)
    cell = harness.resolve(spec, "tiny-ssd.train", root=root)
    assert train.flops_per_token(cell) == 1000.0 * 64
    cell = harness.resolve(spec, "tiny-gqa.open", root=root)
    window = trace_reduce.Trace(window=(0, 10 ** 9), ops=[], host=[],
                                busy_by_device=[1])
    ctx = {"plans": [([0, 3], [8, 1]), ([8, 4], [2, 0])], "trace": window,
           "model": cell.config["model"], "costs": cell.costs,
           "peaks": {"bf16_flops_per_s": 700.0}}
    assert readers.serve_step_mfu(ctx) == pytest.approx(
        100.0 * 7 * (9 + 2) / 1.0 / 700.0)


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 5])
def test_any_block_of_requests_holds_every_quantile_once(seed):
    """Lengths and gaps cycle one seed-drawn order of the block's
    quantiles: every ``BLOCK`` consecutive requests hold the same sizes,
    and two seeds differ only in the order."""
    from bench import traffic
    dist = {"median": 1020, "sigma": 0.5, "min": 16, "max": 3072}
    block = traffic.BLOCK
    ref = sorted(traffic.lengths(dist, block, traffic.rng_for(1, 1)))
    xs = traffic.lengths(dist, 5 * block, traffic.rng_for(seed, 1))
    gaps = np.diff(traffic.arrivals(0.72, 5 * block,
                                    traffic.rng_for(seed, 2)), prepend=0)
    for i in range(4 * block):
        assert sorted(xs[i:i + block]) == ref
        assert sum(gaps[i:i + block]) == pytest.approx(sum(gaps[:block]))
    assert len(set(xs)) == block
