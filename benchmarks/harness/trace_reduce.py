"""From the profiler's `.xplane.pb` to numbers: device busy time, the time of
each jitted module, the operations that took most time, and the longest idle
gaps with what the host was doing in them.  Reads the file with
`jax.profiler.ProfileData` and nothing else."""

from __future__ import annotations

import glob
import os
import re

#: host spans worth naming in an idle gap: the program's one annotation and
#: the harness's own around source and sink calls
HOST_SPANS = ("window_agg.device_step", "bench.sink.write",
              "bench.source.next")


def _union_seconds(intervals) -> float:
    busy, end = 0, None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            busy += stop - start
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy / 1e9


def _gaps(intervals, lo, hi):
    """Idle (start, stop) pairs inside [lo, hi]."""
    gaps, end = [], lo
    for start, stop in sorted(intervals):
        if start > end:
            gaps.append((end, start))
        end = max(end, stop)
    if hi > end:
        gaps.append((end, hi))
    return gaps


def module_name(event_name: str) -> str:
    """`jit__update_step(1234567)` -> `_update_step`."""
    name = re.sub(r"\(.*\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def reduce_planes(planes, extent=None) -> dict:
    """`planes`: [(plane name, [(line name, [(event name, start ns,
    duration ns)])])]; `extent`: (first ns, last ns) of the traced window,
    by default that of the device events.  Device planes are the `/device:`
    planes that have an `XLA Ops` line."""
    devices, host = [], []
    for pname, lines in planes:
        names = {lname for lname, _ in lines}
        if pname.startswith("/device:") and "XLA Ops" in names:
            devices.append((pname, dict(lines)))
        elif pname.startswith("/host:"):
            host += [ev for _, events in lines for ev in events
                     if ev[0] in HOST_SPANS]
    if not devices:
        raise RuntimeError("the trace holds no device plane with XLA Ops")
    every = [ev for _, lines in devices for evs in lines.values()
             for ev in evs]
    lo, hi = extent or (min(s for _, s, _ in every),
                        max(s + d for _, s, d in every))
    busy, ops, modules, gaps = 0.0, {}, {}, []
    for _, lines in devices:
        spans = [(s, s + d) for _, s, d in lines["XLA Ops"]]
        busy += _union_seconds(spans)
        gaps += _gaps(spans, lo, hi)
        for name, _, d in lines["XLA Ops"]:
            ops[name] = ops.get(name, 0) + d
        for name, _, d in lines.get("XLA Modules", []):
            mod = modules.setdefault(module_name(name),
                                     {"seconds": 0.0, "runs": 0})
            mod["seconds"] += d / 1e9
            mod["runs"] += 1

    def doing(gap):
        """The host span that covers most of the gap."""
        best, cover = "host: no annotated span", 0
        for name, s, d in host:
            over = min(gap[1], s + d) - max(gap[0], s)
            if over > cover:
                best, cover = name, over
        return best

    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / len(devices),
        "window_s": (hi - lo) / 1e9,
        "modules": modules,
        "breakdown": {
            "device_ops": [[name[:120], d / 1e9] for name, d in top],
            "idle_gaps": [[doing(g), (g[1] - g[0]) / 1e9] for g in gaps],
        },
    }


def read_planes(path: str):
    """(planes, extent) of an `.xplane.pb`: device planes whole, of the host
    planes only the spans in `HOST_SPANS`.  The extent is over every event of
    every plane, since the runtime's host threads run from the trace's start
    to its end; events of the Python tracer (`$...`) are left out, because
    they go on while the trace is being written out."""
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
                if device or ev.name in HOST_SPANS:
                    events.append((ev.name, start, dur))
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes, (lo, hi)


def reduce_dir(trace_dir: str) -> dict:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return reduce_planes(*read_planes(found[0]))
