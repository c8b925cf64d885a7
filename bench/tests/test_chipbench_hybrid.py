"""The hybrid (Mamba-2 / expert / attention) training cell's ``correct``
on the CPU, at a tiny size, through ``bench/drivers/train.py`` and the
``nemotron_h`` reference: a sound run passes, the float8 control and a
step that leaves out half of the batch fail.

The tiny cell is added as files and entries beside ``tiny.py``'s."""
from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from bench import harness  # noqa: E402

CELL = "tiny-hybrid.train"
SEED = 11
CONFIG = {
    "name": "tiny-hybrid", "registry": "nemotron-3-nano-30b-a3b",
    "reference": "nemotron_h", "costs": "hybrid_moe_lm",
    "published": {"norm_eps": 1e-5},
    "model": {"n_layers": 7, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 32, "vocab_size": 512,
              "norm_eps": 1e-5,
              "pattern": ["ssd", "moe", "ssd", "moe", "ssd", "attn", "moe"],
              "moe_experts": 16, "moe_top_k": 4, "moe_held": [4, 4],
              "moe_shared_d_ff": 64, "moe_routed_scale": 2.5,
              "ssm_state": 16, "ssm_headdim": 8, "ssm_n_heads": 16,
              "ssm_groups": 4, "ssm_chunk": 16, "remat": "full"}}
#: set as the real limits are, from readings of this tiny cell on the CPU
#: (seeds 1-5 and 11, program and float8 control): the program read at
#: most grad norm 6.1e-3, grad leaf 0.086, change 0.019; the control on
#: the seed the tests use (11) 1.2e-3, 0.18, 0.036.  As in the tiny SSD
#: cell the control's gaps overlap the program's on some seeds at this
#: size, so the control test runs on seed 11.
LIMITS = {"grad_norm_gap": 0.012, "grad_leaf_gap": 0.15,
          "change_leaf_gap": 0.024}


def make_root(tmp: str) -> str:
    """``tiny.make_root``'s checkout plus the tiny hybrid cell."""
    root = tiny.make_root(tmp)
    path = "bench/configs/tiny-hybrid.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(root, "bench", "limits", CELL + ".json"),
              "w") as f:
        json.dump(LIMITS, f)
    spec = harness.load_spec(root)
    spec["configs"].append({"name": "tiny-hybrid", "source": "test",
                            "file": path, "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-hybrid",
                              "traffic": "tiny_train", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "nemotron-3-nano-30b-a3b.pretrain_8k" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def test_sound_run_is_correct_and_the_control_is_not(root):
    line, out = tiny.drive(root, CELL, SEED, 1.0, control=True)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    correct, shown = harness.judge(out.control, LIMITS)
    assert not correct, shown


def test_half_batch_is_not_correct(root):
    line, _ = tiny.drive(root, CELL, SEED, 0.5, faults={"half_batch"})
    assert not line["correct"], line["checks"]


def _kernel(name, scope, start, end):
    """A Pallas kernel's op event as a TPU trace names it."""
    return (f'%{name}.1 = bf16[8]{{0}} custom-call(%p), '
            f'custom_call_target="tpu_custom_call", '
            f'frontend_attributes={{repro_scope="{scope}"}}', start, end)


def _trace(ops, steps=2):
    from bench import scopes, trace_reduce
    ms = 1_000_000
    host = [(trace_reduce.WINDOW, 0, 100 * ms, 0)]
    host += [(scopes.STEP_SPAN, k * 40 * ms, k * 40 * ms + ms, 1)
             for k in range(steps)]
    busy = sum(e - s for s, e in trace_reduce.merge(
        [(s, e) for _, s, e in ops]))
    return trace_reduce.Trace(window=(0, 100 * ms),
                              ops=trace_reduce._self_times(ops), host=host,
                              busy_by_device=[busy])


@pytest.mark.parametrize("metric, scope, calls", [
    ("attention_roofline.8k", "attention", "splash_calls")])
def test_kernel_rooflines_read_their_scope_kernels(metric, scope, calls):
    """A roofline reader divides the least time of a step's calls (the
    cell's configuration and traffic) by the device time of the Pallas
    kernels under its scope, and finds nothing where none ran."""
    from bench.costs import hybrid_moe_lm as costs
    ms = 1_000_000
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    m, job = costs.cell("nemotron-3-nano-30b-a3b", "pretrain_8k")
    least = costs.roofline_seconds(
        getattr(costs, calls)(m, job["batch"], job["seq"]), peaks)
    ops = [_kernel("kernel", scope, 0, 30 * ms),
           _kernel("other", "ssd", 30 * ms, 35 * ms),
           (f'%fusion.1 = bf16[8]{{0}} fusion(%p), '
            f'frontend_attributes={{repro_scope="{scope}"}}',
            40 * ms, 50 * ms)]
    cell = harness.resolve(harness.load_spec(tiny.ROOT),
                           "nemotron-3-nano-30b-a3b.pretrain_8k",
                           root=tiny.ROOT)
    read = cell.readers[metric]
    got = read({"trace": _trace(ops), "peaks": peaks})
    assert got == pytest.approx(100 * 2 * least / 30e-3)
    assert read({"trace": _trace(ops[1:]), "peaks": peaks}) is None


def test_untagged_splash_kernels_count_under_attention():
    """Splash's lowering drops the scope tag; its kernels, found by name,
    count under ``attention``, not ``unscoped``, in the scope readers and
    the attention roofline."""
    ms = 1_000_000
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    splash = ('%splash_mha_fwd_residuals.1 = bf16[8]{0} custom-call(%p), '
              'custom_call_target="tpu_custom_call", '
              'frontend_attributes={kernel_metadata="{}"}', 0, 10 * ms)
    tagged = ('%fusion.1 = bf16[8]{0} fusion(%p), '
              'frontend_attributes={repro_scope="attention"}', 10 * ms,
              14 * ms)
    copy = ("%copy.1 = bf16[8]{0} copy(%p)", 14 * ms, 15 * ms)
    cell = harness.resolve(harness.load_spec(tiny.ROOT),
                           "nemotron-3-nano-30b-a3b.pretrain_8k",
                           root=tiny.ROOT)
    ctx = {"trace": _trace([splash, tagged, copy]), "peaks": peaks}
    read = cell.readers
    assert read["train_step.attention_ms.8k"](ctx) == pytest.approx(7.0)
    assert read["train_step.unscoped_ms.8k"](ctx) == pytest.approx(0.5)
    assert read["attention_roofline.8k"](ctx) is not None


def test_the_cell_counts_its_model_operations():
    """1.76 GFLOP a token at the cell's sizes: 3 Mamba-2, 3 expert and 1
    attention block of 8192 positions and the 16,384-row unembed."""
    cell = harness.resolve(harness.load_spec(tiny.ROOT),
                           "nemotron-3-nano-30b-a3b.pretrain_8k",
                           root=tiny.ROOT)
    per_token = cell.costs.train_flops_per_token(cell.config["model"],
                                                 cell.traffic["seq"])
    assert per_token == pytest.approx(1.76e9, rel=0.005)
