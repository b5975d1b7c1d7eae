"""The program's spans beside the device's idle time: per span name the
seconds and count in the traced slice, and the device-idle seconds during
which that span was the innermost one on some host thread.  Reads the same
`.xplane.pb` as `trace_reduce` (with `jax.profiler.ProfileData` and nothing
else) and leaves that module's numbers alone: `reduce_dir` here returns one
more key, `spans`, beside `idle_s` and `idle_unattributed_s`.

Innermost: spans on one thread nest, and at any instant the one opened last
is what the thread is doing; its parents are only where it is doing it.  So
a long gap under `task.process_batch > window_agg.device_step` is the
dispatch's, and the part of `checkpoint.snapshot` that none of its children
covers is the snapshot's own.
"""

from __future__ import annotations

import glob
import os

from . import trace_reduce

#: the program's spans on the cells' path (docs/operations.md "Tracing and
#: latency tracking"), parents and leaves, and the harness's own two
PROGRAM_SPANS = (
    "source.next", "exchange.partition", "exchange.put_wait",
    "task.input_wait", "task.process_batch",
    "window_agg.probe", "window_agg.probe_mirror", "window_agg.mirror",
    "window_agg.stage", "window_agg.device_step",
    "window_agg.fire", "window_agg.fire_dispatch", "window_agg.fire_d2h",
    "window_agg.fire_assemble",
    "checkpoint.align", "checkpoint.snapshot", "window_agg.snapshot",
    "window_agg.snapshot_d2h", "window_agg.snapshot_assemble",
    "checkpoint.complete", "checkpoint.store", "sink.invoke")
HARNESS_SPANS = ("bench.source.next", "bench.sink.write")
SPANS = PROGRAM_SPANS + HARNESS_SPANS


def _merge(intervals):
    """Sorted, disjoint (start, stop) pairs covering the same instants."""
    out = []
    for start, stop in sorted(intervals):
        if stop <= start:
            continue
        if out and start <= out[-1][1]:
            if stop > out[-1][1]:
                out[-1] = (out[-1][0], stop)
        else:
            out.append((start, stop))
    return out


def _both(a, b):
    """Instants in both of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> int:
    return sum(stop - start for start, stop in intervals)


def innermost(events):
    """[(name, start, stop)] segments of one thread's line, each labelled
    with the span opened last among those open: `events` are (name, start
    ns, duration ns) of spans that nest.  A child that overruns its parent
    by a clock's grain is cut at the parent's end."""
    out, stack, at = [], [], None        # stack of [name, stop]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            top = stack.pop()
            out.append((top[0], at, top[1]))
            at = top[1]
        if stack:
            out.append((stack[-1][0], at, start))
        stop = start + dur
        if stack:
            stop = min(stop, stack[-1][1])
        stack.append([name, stop])
        at = start
    while stack:
        top = stack.pop()
        out.append((top[0], at, top[1]))
        at = top[1]
    return [seg for seg in out if seg[2] > seg[1]]


def reduce_spans(planes, extent=None) -> dict:
    """`planes` as `trace_reduce.reduce_planes` takes them, the host lines
    holding the spans of `SPANS`; `extent` likewise.  Returns

    - `spans`: {name: {"seconds", "count", "idle_s"}} — the span's time and
      the events of it that began in the slice, and the device-idle time
      during which it was the innermost span of some host thread;
    - `idle_s`: the slice's device-idle time (mean over devices);
    - `idle_unattributed_s`: idle time with no span of `SPANS` open on any
      thread;
    - `idle_intervals`: {name: [(start ns, stop ns)]} behind `idle_s`, for a
      reader that asks about several spans at once."""
    devices, threads = [], []
    for pname, lines in planes:
        if pname.startswith("/device:") and \
                "XLA Ops" in {lname for lname, _ in lines}:
            devices.append(dict(lines)["XLA Ops"])
        elif pname.startswith("/host:"):
            threads += [[ev for ev in events if ev[0] in SPANS]
                        for _, events in lines]
    if not devices:
        raise RuntimeError("the trace holds no device plane with XLA Ops")
    every = [ev for ops in devices for ev in ops]
    lo, hi = extent or (min(s for _, s, _ in every),
                        max(s + d for _, s, d in every))
    # instants at which EVERY device is idle would hide one busy chip; the
    # cells have one device, and with more the mean is what
    # `device_idle_share` reports
    idle = [_merge(trace_reduce._gaps([(s, s + d) for _, s, d in ops],
                                      lo, hi)) for ops in devices]
    spans, under, covered = {}, {}, []
    for events in threads:
        for name, start, dur in events:
            row = spans.setdefault(name, {"seconds": 0.0, "count": 0,
                                          "idle_s": 0.0})
            row["seconds"] += max(0, min(hi, start + dur)
                                  - max(lo, start)) / 1e9
            row["count"] += lo <= start < hi
        for name, start, stop in innermost(events):
            under.setdefault(name, []).append((start, stop))
            covered.append((start, stop))
    idle_ns = sum(_length(gaps) for gaps in idle) / len(devices)
    intervals = {}
    for name, segments in under.items():
        merged = _merge(segments)
        intervals[name] = [_both(gaps, merged) for gaps in idle]
        spans[name]["idle_s"] = sum(
            _length(part) for part in intervals[name]) / len(devices) / 1e9
    covered = _merge(covered)
    attributed = sum(_length(_both(gaps, covered)) for gaps in idle) \
        / len(devices)
    return {"spans": spans, "idle_s": idle_ns / 1e9,
            "idle_unattributed_s": (idle_ns - attributed) / 1e9,
            "idle_intervals": intervals}


def idle_under(reduced: dict, names) -> float:
    """Device-idle seconds during which a span of `names` was the innermost
    on some thread (two threads inside such spans at once count once)."""
    per_device = None
    for name in names:
        parts = reduced["idle_intervals"].get(name)
        if parts is None:
            continue
        per_device = parts if per_device is None else [
            _merge(a + b) for a, b in zip(per_device, parts)]
    if per_device is None:
        return 0.0
    return sum(_length(part) for part in per_device) / len(per_device) / 1e9


def read_planes(path: str):
    """(planes, extent) as `trace_reduce.read_planes` gives them, with the
    host lines holding the spans of `SPANS` instead of its three."""
    from jax.profiler import ProfileData

    planes, lo, hi = [], None, None
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if ev.name.startswith("$"):
                    continue
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                lo = start if lo is None else min(lo, start)
                hi = start + dur if hi is None else max(hi, start + dur)
                if device or ev.name in SPANS:
                    events.append((ev.name, start, dur))
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes, (lo, hi)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return found[0]


def reduce_dir(trace_dir: str) -> dict:
    """What `trace_reduce.reduce_dir` returns, and the spans beside it."""
    planes, extent = read_planes(find_xplane(trace_dir))
    out = trace_reduce.reduce_planes(planes, extent)
    out.update(reduce_spans(planes, extent))
    return out
