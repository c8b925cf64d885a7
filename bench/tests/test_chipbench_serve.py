"""The serving cell's ``correct`` on the CPU, at a tiny size: a sound run
of the open loop passes, and the float8 control and each fault a serving
cell can have (the cache handed back unwritten; a sampled token altered
where it is produced) fail.  The knee sweep holds a rate on the same
tiny cell.

These drive the whole run with the harness's look for a chip skipped."""
from __future__ import annotations

import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from bench import harness  # noqa: E402

SEED = 2 ** 33 + 5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-gqa.open", {"ttft_p90_ms", "itl_p95_ms", "setup_s"})])
def test_sound_run_is_correct_and_the_control_is_not(root, cell, metrics):
    line, out = tiny.drive(root, cell, SEED, 1.5, control=True)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == metrics
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    limits = harness.resolve(harness.load_spec(root), cell, root=root).limits
    correct, shown = harness.judge(out.control, limits)
    assert not correct, shown


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_a_broken_step_is_not_correct(root, fault):
    line, _ = tiny.drive(root, "tiny-gqa.open", SEED, 1.5, faults={fault})
    assert not line["correct"], line["checks"]


def test_the_sweep_holds_a_rate_and_reads_its_backlog(root):
    from bench import sweep
    row = sweep.hold(tiny.make_run(root, "tiny-gqa.open", SEED, 1.0),
                     rate=20.0, seconds=1.0)
    assert row["rate_per_s"] == 20.0 and row["ticks"] > 0
    assert 0 < row["finished"] <= row["submitted"]
    assert row["tokens_per_s"] > 0 and math.isfinite(row["backlog_slope_per_s"])


def _host_spans_only(path):
    """``trace_reduce.load`` for a CPU profile, which has no TPU plane:
    the window and host spans, no device ops."""
    import glob
    from jax.profiler import ProfileData
    from bench import trace_reduce
    (path,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                        recursive=True)
    host = trace_reduce._host_spans(ProfileData.from_file(path))
    (w0, w1), = [(s, e) for name, s, e, _ in host
                 if name == trace_reduce.WINDOW]
    return trace_reduce.Trace(window=(w0, w1), ops=[],
                              host=[h for h in host if h[2] > w0 and h[1] < w1],
                              busy_by_device=[1])


def test_the_traced_context_counts_by_the_configs_module(root, monkeypatch):
    """The serving driver's traced context carries the configuration's
    ``costs`` module, and the step's share of the peak is its count of
    the traced ticks' plans."""
    from bench import readers, scopes, trace_reduce
    monkeypatch.setattr(trace_reduce, "load", _host_spans_only)
    _, out = tiny.drive(root, "tiny-gqa.open", SEED, 1.5, trace=True)
    ctx = out.layer
    cell = harness.resolve(harness.load_spec(root), "tiny-gqa.open",
                           root=root)
    assert ctx["costs"].__file__ == cell.costs.__file__
    assert ctx["costs"].__file__.endswith(os.path.join("costs",
                                                       "dense_lm.py"))
    assert ctx["plans"]
    ticks = sum(name == scopes.TICK_SPAN for name, *_ in ctx["trace"].host)
    assert ticks >= len(ctx["plans"])
    flops = sum(ctx["costs"].tick_flops(ctx["model"], s, v)
                for s, v in ctx["plans"])
    assert flops > 0
    assert readers.serve_step_mfu(ctx) == pytest.approx(
        100.0 * flops / ctx["trace"].window_s / 197e12)
