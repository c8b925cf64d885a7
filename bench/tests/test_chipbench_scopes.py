"""CPU checks of the per-scope readers (``bench/scopes.py``): each scope's
device self time per traced step, ``unscoped``, that the two add up to
the device time of the window, the program's ``data wait`` spans, and
None where the trace carries no scope tag."""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from bench import harness, scopes, trace_reduce  # noqa: E402

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_tpu.xplane.pb")
MS = 1_000_000          # ns


def _op(name, scope, start, end):
    tag = f', frontend_attributes={{repro_scope="{scope}"}}' if scope else ""
    return (f"%{name} = bf16[8]{{0}} {name}(%p){tag}", start, end)


def _trace(ops, steps=2, waits=()):
    """Two traced steps of synthetic device ops (a loop enclosing two
    tagged ops and an untagged copy) under ``train_step dispatch``."""
    timed = trace_reduce._self_times(ops)
    busy = sum(e - s for s, e in trace_reduce.merge(
        [(s, e) for _, s, e in ops]))
    host = [(trace_reduce.WINDOW, 0, 100 * MS, 0)]
    host += [(scopes.STEP_SPAN, k * 40 * MS, k * 40 * MS + MS, 1)
             for k in range(steps)]
    host += [("next_batch", s, e, 1) for s, e in waits]
    host += [(scopes.DATA_WAIT, s, e, 2) for s, e in waits]
    return trace_reduce.Trace(window=(0, 100 * MS), ops=timed, host=host,
                              busy_by_device=[busy])


OPS = [_op("while.1", "layers", 0, 30 * MS),
       _op("fusion.1", "ssd", 2 * MS, 12 * MS),
       _op("copy.2", None, 12 * MS, 15 * MS),
       _op("fusion.2", "mpx", 20 * MS, 24 * MS),
       _op("fusion.3", "optimizer", 40 * MS, 46 * MS),
       _op("copy.4", None, 50 * MS, 51 * MS)]


@pytest.mark.parametrize("scope, ms", [
    ("ssd", 5.0), ("layers", 6.5), ("mpx", 2.0), ("optimizer", 3.0),
    ("unscoped", 2.0), ("unembed_loss", 0.0)])
def test_scope_ms_per_step(scope, ms):
    """Self time: the loop keeps what its tagged and untagged children
    leave (30 - 10 - 3 - 4 = 13 ms), and each op's time counts once."""
    ctx = {"trace": _trace(OPS)}
    assert scopes.scope_reader(scope)(ctx) == pytest.approx(ms)


def test_scopes_and_unscoped_add_up_to_the_busy_time():
    trace = _trace(OPS)
    total = sum(scopes.self_ns_by_scope(trace.ops).values())
    assert total == trace.busy_by_device[0] == 37 * MS
    per_step = sum(scopes.scope_ms({"trace": trace}, s)
                   for s in scopes.self_ns_by_scope(trace.ops))
    assert per_step == pytest.approx(trace.busy_s * 1e3 / 2)


def test_scope_of_reads_the_tag_in_an_op_name():
    assert scopes.scope_of(OPS[1][0]) == "ssd"
    assert scopes.scope_of(OPS[2][0]) == scopes.UNSCOPED
    kernel = ('%paged_attention.1 = bf16[16,384,128] custom-call(%a), '
              'custom_call_target="tpu_custom_call", frontend_attributes='
              '{kernel_metadata={},repro_scope="attention"}')
    assert scopes.scope_of(kernel) == "attention"


def test_nothing_to_read_without_tags_or_steps():
    untagged = [(n.split(",")[0], s, e) for n, s, e in OPS]
    assert scopes.scope_ms({"trace": _trace(untagged)}, "unscoped") is None
    assert scopes.scope_ms({"trace": _trace(OPS, steps=0)}, "ssd") is None
    assert scopes.scope_ms({}, "ssd") is None


def test_no_tags_on_a_recorded_tpu_trace():
    """The recorded trace predates the scopes: every reader finds
    nothing, as on a program without them."""
    trace = trace_reduce.load(TRACE)
    trace.host.append((scopes.STEP_SPAN, *trace.window, 1))
    ctx = {"trace": trace}
    assert set(scopes.self_ns_by_scope(trace.ops)) == {scopes.UNSCOPED}
    for scope in ("ssd", "layers", "unembed_loss", "mpx", "optimizer",
                  "unscoped"):
        assert scopes.scope_ms(ctx, scope) is None
    assert scopes.data_wait_ms(ctx) is None


def test_data_wait_ms_per_step():
    waits = [(10 * MS, 10 * MS + 300_000), (50 * MS, 50 * MS + 100_000)]
    ctx = {"trace": _trace(OPS, waits=waits)}
    assert scopes.data_wait_ms(ctx) == pytest.approx(0.2)
    assert scopes.data_wait_ms({"trace": _trace(OPS)}) is None


def test_the_train_cell_reads_every_scope_metric():
    spec = harness.load_spec(tiny.ROOT)
    cell = harness.resolve(spec, "mamba2-130m.pretrain_2k")
    names = {f"train_step.{s}_ms" for s in
             ("ssd", "layers", "unembed_loss", "mpx", "optimizer",
              "unscoped")} | {"train.data_wait_ms"}
    assert names <= set(cell.readers)
    ctx = {"trace": _trace(OPS, waits=[(10 * MS, 11 * MS)])}
    values = {n: cell.readers[n](ctx) for n in names}
    assert all(v is not None for v in values.values()), values


SERVE_OPS = [_op("fusion.1", "embed", 0, 1 * MS),
             _op("while.1", "layers", 1 * MS, 20 * MS),
             _op("fusion.2", "attention", 2 * MS, 6 * MS),
             _op("paged_attention.1", "attention", 6 * MS, 9 * MS),
             _op("fusion.3", "mlp", 10 * MS, 16 * MS),
             _op("copy.1", None, 20 * MS, 21 * MS),
             _op("fusion.4", "unembed_loss", 21 * MS, 23 * MS),
             _op("fusion.5", "sampling", 23 * MS, 24 * MS),
             _op("fusion.6", "mlp", 50 * MS, 54 * MS)]


def _serve_trace(ops, ticks=2):
    """Device ops of ``ticks`` engine ticks under ``engine tick`` spans."""
    timed = trace_reduce._self_times(ops)
    busy = sum(e - s for s, e in trace_reduce.merge(
        [(s, e) for _, s, e in ops]))
    host = [(trace_reduce.WINDOW, 0, 100 * MS, 0)]
    host += [(scopes.TICK_SPAN, k * 40 * MS, k * 40 * MS + 30 * MS, 1)
             for k in range(ticks)]
    return trace_reduce.Trace(window=(0, 100 * MS), ops=timed, host=host,
                              busy_by_device=[busy])


SERVE_METRICS = {"attention": "serve_step.attention_ms.open",
                 "mlp": "serve_step.mlp_ms.open",
                 "layers": "serve_step.layers_ms.open",
                 "unscoped": "serve_step.unscoped_ms.open"}


@pytest.mark.parametrize("scope, ms", [
    ("attention", 3.5), ("mlp", 5.0), ("layers", 3.0), ("unscoped", 0.5)])
def test_serve_scope_ms_per_tick(scope, ms):
    """The loop keeps what its children leave (19 - 4 - 3 - 6 = 6 ms);
    per tick over the two ``engine tick`` spans."""
    cell = harness.resolve(harness.load_spec(tiny.ROOT),
                           "starcoder2-3b.chat_open")
    read = cell.readers[SERVE_METRICS[scope]]
    assert read({"trace": _serve_trace(SERVE_OPS)}) == pytest.approx(ms)


def test_serve_scopes_add_up_to_the_busy_time_of_a_tick():
    """The four serving metrics with ``embed``, ``unembed_loss`` and
    ``sampling`` are the device busy time per tick."""
    trace = _serve_trace(SERVE_OPS)
    ctx = {"trace": trace}
    total = sum(scopes.scope_ms(ctx, s, scopes.TICK_SPAN) for s in
                ("attention", "mlp", "layers", "unscoped", "embed",
                 "unembed_loss", "sampling"))
    assert total == pytest.approx(trace.busy_s * 1e3 / 2)


def test_tick_and_step_readers_count_only_their_own_spans():
    """A serving trace has no training step, a training trace no tick:
    each reader finds nothing in the other's trace."""
    serve = {"trace": _serve_trace(SERVE_OPS)}
    train = {"trace": _trace(OPS)}
    assert scopes.scope_ms(serve, "layers") is None
    assert scopes.scope_ms(train, "layers", scopes.TICK_SPAN) is None
    assert scopes.scope_ms(train, "layers") == pytest.approx(6.5)
    no_ticks = {"trace": _serve_trace(SERVE_OPS, ticks=0)}
    assert scopes.scope_ms(no_ticks, "attention", scopes.TICK_SPAN) is None


def test_no_serve_tags_on_a_recorded_tpu_trace():
    trace = trace_reduce.load(TRACE)
    trace.host.append((scopes.TICK_SPAN, *trace.window, 1))
    cell = harness.resolve(harness.load_spec(tiny.ROOT),
                           "starcoder2-3b.chat_open")
    for name in SERVE_METRICS.values():
        assert cell.readers[name]({"trace": trace}) is None
