"""Reduce a ``jax.profiler`` trace to device busy time, per-op time and
labelled idle gaps.

A TPU trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``)
holds one plane per chip, ``/device:TPU:<n>``, whose ``XLA Ops`` line
has one event per executed HLO op (its name is the op's HLO text, e.g.
``%fusion.3 = bf16[...] fusion(...)``), and a ``/host:CPU`` plane whose
``python3`` line carries the ``jax.profiler.TraceAnnotation`` spans the
benchmark opens: ``bench_window`` around the traced part of the window
and one span per step or engine tick.  Times are nanoseconds from the
start of the profile.

- busy: the union of the device-op intervals inside the window, summed;
- ops: device self time per op (an op that encloses others, such as a
  loop, is charged only what its children leave), keyed by the op's
  name before `` = `` with its trailing ``.<n>`` removed (``%fusion.3``
  -> ``fusion``); a Pallas kernel keeps its HLO name;
- gaps: intervals of the window in which no op ran, labelled by the
  innermost host span that covers the gap's middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench_window"
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?: =|$)")


def op_key(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion``; a Pallas
    custom call -> ``custom-call:<its HLO name>``."""
    m = _OP_NAME.match(name)
    key = m.group(1) if m else name.split(" ", 1)[0]
    if "tpu_custom_call" in name:
        key = "custom-call:" + key
    return key


def is_kernel(name: str) -> bool:
    """A Pallas TPU kernel (Mosaic custom call)."""
    return "custom-call(" in name and "tpu_custom_call" in name


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Trace:
    """One chip's device ops and the host spans, cut to the window."""
    window: tuple                     # (start_ns, end_ns)
    ops: list                         # (name, start, end, self_ns), chip 0
    host: list                        # (name, start_ns, end_ns, depth)
    n_devices: int = 1
    busy_by_device: list = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips traced."""
        return sum(self.busy_by_device) / len(self.busy_by_device) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self) -> dict:
        out: dict = {}
        for name, _, _, own in self.ops:
            k = op_key(name)
            out[k] = out.get(k, 0.0) + own * 1e-9
        return out

    def kernel_seconds(self) -> float:
        return sum(own for name, _, _, own in self.ops
                   if is_kernel(name)) * 1e-9

    def gaps(self) -> list:
        """(label, seconds) of every idle interval of the window."""
        w0, w1 = self.window
        busy = merge([(s, e) for _, s, e, _ in self.ops])
        out, t = [], w0
        for s, e in busy + [(w1, w1)]:
            if s > t:
                out.append((self.label(t, s), (s - t) * 1e-9))
            t = max(t, e)
        return out

    def label(self, t0: int, t1: int) -> str:
        """The innermost host span covering the middle of [t0, t1]."""
        mid = (t0 + t1) // 2
        best, depth = "host: no span", -1
        for name, s, e, d in self.host:
            if s <= mid < e and d > depth and name != WINDOW:
                best, depth = name, d
        return best

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gaps(), key=lambda g: -g[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def _self_times(events):
    """(name, start, end, self) with each enclosing event charged only
    the time its nested events leave."""
    events = sorted(events, key=lambda x: (x[1], -x[2]))
    own = [e - s for _, s, e in events]
    stack = []
    for i, (_, s, e) in enumerate(events):
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(n, s, e, max(o, 0)) for (n, s, e), o in zip(events, own)]


def _host_spans(profile):
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            stack = []
            for name, s, e in sorted(_events(line), key=lambda x: (x[1], -x[2])):
                while stack and stack[-1] <= s:
                    stack.pop()
                spans.append((name, s, e, len(stack)))
                stack.append(e)
    return spans


def load(path: str) -> Trace:
    """The trace under ``path`` (a directory searched for one
    ``.xplane.pb``, or the file itself), cut to its ``bench_window``."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise FileNotFoundError(f"expected one .xplane.pb under {path}, "
                                    f"found {files}")
        path = files[0]
    profile = ProfileData.from_file(path)
    host = _host_spans(profile)
    windows = [(s, e) for name, s, e, _ in host if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    per_device = []
    for plane in sorted(profile.planes, key=lambda p: p.name):
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += _self_times([(n, max(s, w0), min(e, w1))
                                    for n, s, e in _events(line)
                                    if e > w0 and s < w1])
        per_device.append(ops)
    if not per_device:
        raise ValueError(f"no TPU device plane in {path}")
    busy = [sum(e - s for s, e in merge([(s, e) for _, s, e, _ in ops]))
            for ops in per_device]
    return Trace(window=(w0, w1), ops=per_device[0],
                 host=[h for h in host if h[2] > w0 and h[1] < w1],
                 n_devices=len(per_device), busy_by_device=busy)
