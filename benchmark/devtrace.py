"""Device time from a JAX profiler trace.

The run opens a host span named `bench_window` around the traced part of
its window. From the trace this reads, within that span:

- busy: the union of the intervals in which an operation ran on each
  device (its `XLA Ops` line; its `XLA Modules` line where there is no
  ops line), averaged over the devices;
- kernel time: the device time of each jitted program, by name (the
  `XLA Modules` line; the `(id)` suffix of a module's name dropped);
- op time: the device time of each operation, by its HLO name (the text
  before ` = `);
- idle gaps: the intervals in which device 0 ran nothing, each labelled
  with the innermost host span open across its middle, or `host idle`.

Device planes are those named `/device:TPU:<n>`; every other plane is
the host's. Where the profiler ran out of trace buffers (a `Trace Buffers
Dropped` event on a device line), the window ends where the drop begins,
so that nothing unrecorded reads as idle. The reduction works on plain
tuples, so it is tested on a recorded trace without a chip.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench_window"
DROPPED = "Trace Buffers Dropped"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    kernels: dict = field(default_factory=dict)  # name -> device seconds
    ops: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)  # (label, seconds), longest first
    dropped: bool = False

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}

    def summary(self) -> str:
        return (f"{self.devices} device(s), window {self.window_s:.6f}s"
                f"{' (cut where trace buffers were dropped)' if self.dropped else ''}"
                f", busy {self.busy_s:.6f}s, kernels "
                + ", ".join(f"{k}={v:.6f}s" for k, v in
                            sorted(self.kernels.items(), key=lambda kv: -kv[1])))


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _label(host, t) -> str:
    """The innermost (shortest) host span open at time t."""
    best = None
    for s, e, name in host:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "host idle"


def reduce_planes(planes) -> Reduced | None:
    """`planes`: [(plane name, [(line name, events)])], events an iterable
    (iterable again) of (name, start_ns, duration_ns). None when the
    trace has no window span or no device plane."""
    window = None
    host = []
    devices = []
    for pname, lines in planes:
        if _DEVICE.match(pname):
            devices.append((pname, lines))
            continue
        for _, events in lines:
            for name, start, dur in events:
                if name == WINDOW_SPAN:
                    window = (start, start + dur)
                elif dur > 0:
                    host.append((start, start + dur, name))
    if window is None or not devices:
        return None
    w0, w1 = window
    dropped = False
    for _, lines in devices:
        for line, events in lines:
            if line == "XLA Ops":
                continue  # drops are marked on the trace-marker line
            for name, start, _ in events:
                if name == DROPPED and start < w1:
                    w1, dropped = max(w0, start), True
                    break
    busy_total = 0.0
    kernels: dict = {}
    ops: dict = {}
    gaps = []
    for n, (_, lines) in enumerate(sorted(devices, key=lambda d: d[0])):
        by_line = dict(lines)
        op_line = by_line.get("XLA Ops") or by_line.get("XLA Modules") or ()
        spans = []
        for name, start, dur in op_line:
            s, e = max(start, w0), min(start + dur, w1)
            if e > s:
                spans.append((s, e))
                key = name.split(" = ", 1)[0]
                ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        busy = _union(spans)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for name, start, dur in by_line.get("XLA Modules", ()):
            s, e = max(start, w0), min(start + dur, w1)
            if e > s:
                key = _SUFFIX.sub("", name)
                kernels[key] = kernels.get(key, 0.0) + (e - s) * 1e-9
        if n == 0:
            edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((_label(host, (s + e) / 2), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced((w1 - w0) * 1e-9, busy_total / len(devices), len(devices),
                   kernels, ops, gaps, dropped)


class _Events:
    """The events of one profiler line, read anew on each pass."""

    def __init__(self, line, owner):
        self.line = line
        self.owner = owner  # the ProfileData the line belongs to

    def __iter__(self):
        for e in self.line.events:
            yield e.name, e.start_ns, e.duration_ns


def load(path: str):
    """The planes of one `.xplane.pb`, in the form `reduce_planes` reads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, _Events(ln, pd)) for ln in p.lines])
            for p in pd.planes]


def reduce_dir(trace_dir: str) -> Reduced | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    return reduce_planes(load(paths[-1]))
