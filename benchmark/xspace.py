"""Device time per named scope, and device idle time per program span,
from a JAX profiler trace.

`devtrace.py` reads a trace through `jax.profiler.ProfileData`, which
gives each event its name, start and duration but not its metadata's
stats. On a TPU the scope an operation was traced under is such a stat:
the device plane's event metadata for an `XLA Ops` event carries `tf_op`,
the operation's op-name path (`jit(f)/bls/final_exp/while/body/.../
bls/product_tree/dot_general:`). So this module reads the `.xplane.pb`
itself, with a schema of the XPlane protobuf's fields it needs, and keeps
the times as `ProfileData` gives them (line `timestamp_ns` plus the
event's offset in whole ns), so the window and the busy time agree with
`devtrace.py` to the nanosecond.

Within the host span `bench_window` (cut where trace buffers were
dropped, as `devtrace.py` does), on device 0:

- scopes: self time per `bls/<stage>` scope, the innermost `bls/` name of
  the op's `tf_op`. Each instant of busy time goes to the innermost op
  event covering it (the one that started last): the `XLA Ops` line
  nests a loop's body ops inside its `%while` event, so self time counts
  nothing twice. Ops with no `bls/` scope go under `(unscoped)`.
- idle_by_span: for each program span name (PROGRAM_PREFIXES), the idle
  seconds during which a span of that name was open on any host thread;
  idle_unattributed_s, the idle seconds with no program span open.

The reduction works on plain tuples, so it is tested on a recorded trace
without a chip.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import devtrace

UNSCOPED = "(unscoped)"
PROGRAM_PREFIXES = ("lane/", "stage/", "supervisor/", "compile/", "device/",
                    "lifecycle/")
_SCOPE = re.compile(r"(?:^|/)(bls/[A-Za-z0-9_]+)")


def scope_of(tf_op: str | None) -> str:
    """The innermost `bls/<stage>` name scope of an op-name path."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else UNSCOPED


def _measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a, b):
    """Intersection of two sorted lists of disjoint [s, e) intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def sweep(ops, w0, w1):
    """({scope: ns}, idle) over `ops`, (start, end, scope) clipped to the
    window [w0, w1]: each covered instant goes to the innermost op open
    at it, the one that started last, so the scopes sum to the union of
    the ops' intervals; idle is the rest of the window, as [s, e) pairs."""
    out: dict = {}
    idle = []
    stack: list = []  # (end, scope), the innermost last
    t = w0
    for s, e, scope in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and t < s:
            end, top = stack[-1]
            stop = end if end < s else s
            if stop > t:
                out[top] = out.get(top, 0) + (stop - t)
                t = stop
            if end > s:
                break
            stack.pop()
        if not stack and s > t:
            idle.append((t, s))
        if s > t:
            t = s
        stack.append((e, scope))
    while stack:
        end, top = stack.pop()
        if end > t:
            out[top] = out.get(top, 0) + (end - t)
            t = end
    if w1 > t:
        idle.append((t, w1))
    return out, idle


@dataclass
class Scoped:
    window_s: float
    busy_s: float  # device 0
    scopes: dict = field(default_factory=dict)  # scope -> device-0 seconds
    idle_by_span: dict = field(default_factory=dict)  # span name -> seconds
    idle_unattributed_s: float = 0.0
    idle: list = field(default_factory=list)  # device-0 idle [s, e) ns
    spans: dict = field(default_factory=dict)  # name -> union [s, e) ns

    def idle_under(self, names) -> float:
        """Device-0 idle seconds inside the union of the spans `names`."""
        cover = devtrace._union(iv for n in names
                                for iv in self.spans.get(n, ()))
        return _measure(_intersect(self.idle, cover)) * 1e-9

    def summary(self) -> str:
        idle_s = _measure(self.idle) * 1e-9
        top = sorted(self.scopes.items(), key=lambda kv: -kv[1])
        by = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        return (f"device 0 busy {self.busy_s:.6f}s of {self.window_s:.6f}s; "
                "scopes " + ", ".join(f"{k}={v:.6f}s" for k, v in top)
                + f"; idle {idle_s:.6f}s, unattributed "
                f"{self.idle_unattributed_s:.6f}s, by span "
                + ", ".join(f"{k}={v:.6f}s" for k, v in by))


def reduce_planes(planes) -> Scoped | None:
    """`planes` as `devtrace.reduce_planes` takes them, each event
    (name, start_ns, duration_ns[, scope]); an op with no scope is
    `(unscoped)`. None when the trace has no window span or no device
    plane."""
    window = None
    host: dict = {}
    devices = []
    for pname, lines in planes:
        if devtrace._DEVICE.match(pname):
            devices.append((pname, lines))
            continue
        for _, events in lines:
            for name, start, dur, *_ in events:
                if name == devtrace.WINDOW_SPAN:
                    window = (start, start + dur)
                elif dur > 0 and name.startswith(PROGRAM_PREFIXES):
                    host.setdefault(name, []).append((start, start + dur))
    if window is None or not devices:
        return None
    w0, w1 = window
    for _, lines in devices:
        for line, events in lines:
            if line == "XLA Ops":
                continue
            for name, start, *_ in events:
                if name == devtrace.DROPPED and start < w1:
                    w1 = max(w0, start)
                    break
    _, lines = min(devices, key=lambda d: d[0])
    by_line = dict(lines)
    ops = []
    for ev in by_line.get("XLA Ops") or by_line.get("XLA Modules") or ():
        s = ev[1]
        e = s + ev[2]
        if s < w0:
            s = w0
        if e > w1:
            e = w1
        if e > s:
            ops.append((s, e, (ev[3] if len(ev) > 3 else None) or UNSCOPED))
    scopes, idle = sweep(ops, w0, w1)
    spans = {}
    for name, ivs in host.items():
        clipped = [(max(s, w0), min(e, w1)) for s, e in ivs]
        spans[name] = devtrace._union(iv for iv in clipped if iv[1] > iv[0])
    by_span = {n: _measure(_intersect(idle, u)) * 1e-9
               for n, u in spans.items()}
    covered = _intersect(idle, devtrace._union(
        iv for u in spans.values() for iv in u))
    return Scoped(
        (w1 - w0) * 1e-9, sum(scopes.values()) * 1e-9,
        {k: v * 1e-9 for k, v in scopes.items()},
        {n: v for n, v in by_span.items() if v > 0},
        (_measure(idle) - _measure(covered)) * 1e-9, idle, spans)


# -- reading a .xplane.pb ------------------------------------------------------


def _schema():
    """Message classes for the fields of tsl's xplane.proto read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xspace.proto", package="benchmark_xspace",
        syntax="proto3")

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if type_name:
                f.type_name = ".benchmark_xspace." + type_name

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    message("XStat", ("metadata_id", 1, F.TYPE_INT64, one, None),
            ("str_value", 5, F.TYPE_STRING, one, None))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, one, None),
            ("offset_ps", 2, F.TYPE_INT64, one, None),
            ("duration_ps", 3, F.TYPE_INT64, one, None))
    message("XLine", ("name", 2, F.TYPE_STRING, one, None),
            ("timestamp_ns", 3, F.TYPE_INT64, one, None),
            ("events", 4, F.TYPE_MESSAGE, many, "XEvent"))
    message("XEventMetadata", ("name", 2, F.TYPE_STRING, one, None),
            ("stats", 5, F.TYPE_MESSAGE, many, "XStat"))
    message("XStatMetadata", ("name", 2, F.TYPE_STRING, one, None))
    # map<int64, ...> fields, read as their wire form: entries of key, value
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
            ("value", 2, F.TYPE_MESSAGE, one, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
            ("value", 2, F.TYPE_MESSAGE, one, "XStatMetadata"))
    message("XPlane", ("name", 2, F.TYPE_STRING, one, None),
            ("lines", 3, F.TYPE_MESSAGE, many, "XLine"),
            ("event_metadata", 4, F.TYPE_MESSAGE, many, "EventMetadataEntry"),
            ("stat_metadata", 5, F.TYPE_MESSAGE, many, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, F.TYPE_MESSAGE, many, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark_xspace.XSpace"))


def load(path: str):
    """The planes of one `.xplane.pb` in the form `reduce_planes` reads,
    with each device op's scope from its `tf_op` stat."""
    with open(path, "rb") as f:
        space = _schema().FromString(f.read())
    planes = []
    for plane in space.planes:
        device = bool(devtrace._DEVICE.match(plane.name))
        tf_op = {e.key for e in plane.stat_metadata
                 if e.value.name == "tf_op"}
        names, scopes = {}, {}
        for entry in plane.event_metadata:
            names[entry.key] = entry.value.name
            if device:
                scopes[entry.key] = scope_of(next(
                    (s.str_value for s in entry.value.stats
                     if s.metadata_id in tf_op), None))
        lines = []
        for line in plane.lines:
            t0 = line.timestamp_ns
            ops = device and line.name == "XLA Ops"
            lines.append((line.name, [
                (names.get(e.metadata_id, ""), t0 + e.offset_ps // 1000,
                 e.duration_ps // 1000,
                 scopes.get(e.metadata_id) if ops else None)
                for e in line.events]))
        planes.append((plane.name, lines))
    return planes


def read_dir(trace_dir: str) -> Scoped | None:
    """The reduction of the newest trace under `trace_dir`, as
    `devtrace.reduce_dir` picks it."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return reduce_planes(load(paths[-1])) if paths else None


def of_run(ctx) -> Scoped | None:
    """The reduction of a `--trace 1` run's trace, read once per run and
    kept in `ctx`: `run.py` leaves the trace in `.bench_cache/trace` of
    the checkout until the next traced run. Its summary is printed as the
    run's table of device time per scope and idle time per span."""
    if ctx.get("trace") is None:
        return None
    if "xspace" not in ctx:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ctx["xspace"] = read_dir(os.path.join(root, ".bench_cache", "trace"))
        if ctx["xspace"] is not None:
            print("trace: " + ctx["xspace"].summary(), flush=True)
    return ctx["xspace"]


def scope_ms_per_kset(ctx, scopes) -> float | None:
    """Device-0 self time under `scopes` in the traced span, scaled to the
    window as `kernel_ms_per_kset` scales kernel time, in ms per 1,000
    signature sets verdicted in the window."""
    tr = of_run(ctx)
    if tr is None or not ctx["sets_done"] or tr.window_s <= 0:
        return None
    s = sum(tr.scopes.get(k, 0.0) for k in scopes) / tr.window_s
    return s * ctx["window_s"] * 1e3 / (ctx["sets_done"] / 1e3)
