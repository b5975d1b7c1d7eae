"""Metric arithmetic.  `Context` holds what one run measured; the end-to-end
metrics are taken from the benchmark's own clock stamps (generator and
sink), the per-layer readers from the program's counters (as a difference
between the window's two ends) and from the reduced device trace.

A per-layer reader is named by a file in `layer_metrics/` together with its
parameters, so a metric an existing reader can compute is added as data.  A
reader that finds nothing to read returns None and the metric is left out.
"""

from __future__ import annotations

import json
import os

import numpy as np

from . import roofline

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return table[device_kind]


class Context:
    def __init__(self, config, stream, clock, source, delivered, snaps,
                 cuts_in_window, device_kind):
        self.config = config
        self.stream = stream
        self.clock = clock
        self.source = source
        self.delivered = delivered
        self.snaps = snaps
        self.cuts_in_window = cuts_in_window
        self.device_kind = device_kind
        self.trace = None

    # -- what the generator's log says --------------------------------------
    def _log(self):
        return [row for rows in self.source.log for row in rows]

    def attempted_and_late(self):
        """(events attempted in the window, events due in it that the job
        had not taken in when it closed)."""
        batch, t_end = self.stream.batch, self.clock.t_end
        if self.stream.mode == "paced":
            # a batch due in the window's last interval cannot have been
            # taken in by the close; every earlier one can
            interval = batch / self.stream.rate
            due = [r for r in self._log() if r[1] <= t_end]
            late = [r for r in due if r[1] <= t_end - interval
                    and (r[3] is None or r[3] > t_end)]
            return len(due) * batch, len(late) * batch
        handed = [r for r in self._log()
                  if r[3] is not None and r[3] <= t_end]
        return len(handed) * batch, 0

    def handed_per_second(self):
        """Batches handed to the job in each second of the window: where a
        fire or a checkpoint held the sources back shows as a dip."""
        t0, t_end = self.clock.t0, self.clock.t_end
        counts = [0] * int(np.ceil(self.clock.seconds))
        for r in self._log():
            if r[3] is not None and r[3] <= t_end:
                counts[min(len(counts) - 1, int(r[3] - t0))] += 1
        return counts

    # -- end-to-end ----------------------------------------------------------
    def setup_s(self):
        return self.clock.t0 - self.clock.started

    def records_per_s(self):
        """All events the source tasks handed to the job inside the window,
        over the window's length."""
        return self.attempted_and_late()[0] / self.clock.seconds

    def _result_latencies_ms(self):
        """Per row of every window that closed inside the measured window:
        the sink's stamp minus the wall time at which the window closed."""
        st, clock = self.stream, self.clock
        parts = []
        for stamp, cols in self.delivered:
            ends = cols["window_end"]
            closes = clock.t0 + (ends - st.base_ms) / 1000.0
            due = (ends > st.base_ms) & (closes <= clock.t_end)
            if due.any():
                parts.append((stamp - closes[due]) * 1000.0)
        if not parts:
            raise RuntimeError("no window closed inside the measured window")
        return np.concatenate(parts)

    def result_latency_p50_ms(self):
        return float(np.percentile(self._result_latencies_ms(), 50))

    def end_to_end(self, cell: dict) -> dict:
        return {name: (getattr(self, name)(), unit)
                for name, unit in cell["end_to_end"].items()}

    # -- counters, as a difference over the window ---------------------------
    def delta(self, group: str, key: str, span=("t0", "t_end")) -> int:
        return sum(b[key] - a[key] for a, b in
                   zip(self.snaps[span[0]][group], self.snaps[span[1]][group]))

    def phase_ns(self, phases) -> int:
        return sum(b.get(p, 0) - a.get(p, 0) for p in phases for a, b in
                   zip(self.snaps["t0"]["phase_ns"],
                       self.snaps["t_end"]["phase_ns"]))

    def fires_in_window(self) -> int:
        """Window fires whose first rows arrived inside the window, counted
        once per window subtask."""
        first = {}
        for stamp, cols in self.delivered:
            for end in np.unique(cols["window_end"]).tolist():
                first[end] = min(stamp, first.get(end, stamp))
        inside = [e for e, s in first.items()
                  if self.clock.t0 < s <= self.clock.t_end]
        return len(inside) * self.config["parallelism"]


# -- per-layer readers --------------------------------------------------------

def generator_late_percentile(ctx, percentile):
    """How late the open-loop generator sent, ms (sent minus due)."""
    late = [(r[2] - r[1]) * 1000.0 for r in ctx._log()
            if r[1] is not None and r[1] <= ctx.clock.t_end]
    return float(np.percentile(late, percentile)) if late else None


def result_latency_percentile(ctx, percentile):
    """A percentile of the result latency over every row due in the window,
    ms (the same rows as `result_latency_p50_ms`)."""
    return float(np.percentile(ctx._result_latencies_ms(), percentile))


def task_time_share(ctx, role, part):
    """`part` (busy | idle | backpressure) as a share of the three, over the
    `role` (source | window) tasks, %."""
    parts = {p: ctx.delta(role, f"{p}_ns")
             for p in ("busy", "idle", "backpressure")}
    total = sum(parts.values())
    return 100.0 * parts[part] / total if total else None


def phase_ms_per_mrec(ctx, phases):
    """Host self time of the operator phases, ms per million records in."""
    records = ctx.delta("window", "records_in")
    return ctx.phase_ns(phases) / 1e6 / (records / 1e6) if records else None


def phase_ms_per_fire(ctx, phase):
    fires = ctx.fires_in_window()
    return ctx.phase_ns([phase]) / 1e6 / fires if fires else None


def phase_ms_per_checkpoint(ctx, phase):
    """Per checkpoint asked for (and completed) inside the window, summed
    over the window subtasks."""
    cuts = ctx.cuts_in_window
    return ctx.phase_ns([phase]) / 1e6 / cuts if cuts else None


def module_roofline(ctx, module, work):
    """The least time the chip could take for the records the window tasks
    took in over the traced slice, over the device time of the traced
    `module`, %.  `work` names a function of `roofline.py` that reads only
    the configuration.  The metric is listed only for cells that run the
    module, so a trace without it is an error and not a silent gap."""
    found = ctx.trace["modules"].get(module)
    if not found or not found["seconds"]:
        raise RuntimeError(
            f"no device time of module {module!r} in the trace; it holds "
            f"{sorted(ctx.trace['modules'])}")
    events = ctx.delta("window", "records_in", ("trace0", "trace1"))
    if not events:
        return None
    peaks = load_peaks(ctx.device_kind)
    need = getattr(roofline, work)(ctx.config)
    least = max(events * need["bytes"] / peaks["hbm_bytes_per_s"],
                events * need["flops"] / peaks["flops_per_s"])
    return 100.0 * least / found["seconds"]


def device_idle_share(ctx):
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
