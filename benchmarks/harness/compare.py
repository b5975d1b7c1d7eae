"""The comparison that decides `correct`: every row the timed job delivered
to the sink, against the plain reference over the regenerated events.

Numbers compared (each has a limit in the cell's file):

    rows_missing     (key, window) cells the reference has and the sink lacks
    rows_unexpected  rows the reference does not have: unknown key, bad
                     window bounds, a window that should not exist, or a
                     (key, window) delivered a second time
    count_mismatch   cells whose count field differs (exact)
    minmax_mismatch  cells whose f32 min or max differs (exact)
    sum_rel_gap      widest |sum - reference| / max(|reference|, 1)

`control_rows` puts the reference in the program's place with one thing
lowered or broken, to show the comparison fails when it should.
"""

from __future__ import annotations

import numpy as np


def group_by_window(batches) -> dict:
    """{window_end: [columns, ...]} of the sink's (stamp, columns) list."""
    groups = {}
    for _stamp, cols in batches:
        ends = cols["window_end"]
        if ends.size == 0:
            continue
        if ends.min() == ends.max():
            groups.setdefault(int(ends[0]), []).append(cols)
            continue
        for end in np.unique(ends).tolist():
            sel = ends == end
            groups.setdefault(int(end), []).append(
                {k: v[sel] for k, v in cols.items()})
    return groups


class Comparison:
    def __init__(self, stream, fields: dict):
        self.stream = stream
        self.fields = fields
        self.numbers = {"rows_missing": 0, "rows_unexpected": 0}
        kinds = set(fields.values())
        if "count" in kinds:
            self.numbers["count_mismatch"] = 0
        if kinds & {"min", "max"}:
            self.numbers["minmax_mismatch"] = 0
        if "sum" in kinds:
            self.numbers["sum_rel_gap"] = 0.0
        self.rows_compared = 0
        self.windows_compared = 0
        #: events of windows that owed rows and delivered none
        self.events_of_lost_windows = 0

    def window(self, end_ms: int, ref, parts) -> None:
        """One window: `ref` is the reference's {kind: array over the
        universe} (None when it has no event there), `parts` the delivered
        column dicts."""
        st, num = self.stream, self.numbers
        if ref is None:
            num["rows_unexpected"] += sum(p["k"].size for p in parts)
            return
        present = ref["count"] > 0
        if not parts:
            num["rows_missing"] += int(present.sum())
            self.events_of_lost_windows += int(ref["count"].sum())
            return
        self.windows_compared += 1
        cols = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        keys = cols["k"]
        pos = np.minimum(np.searchsorted(st.universe, keys), st.n_keys - 1)
        good = (st.universe[pos] == keys) \
            & (cols["window_start"] == end_ms - st.size_ms)
        pos_good = pos[good]
        first = np.zeros(st.n_keys, np.int64)
        times = np.bincount(pos_good, minlength=st.n_keys)
        got = times > 0
        num["rows_unexpected"] += int((~good).sum()) \
            + int((times[got] - 1).sum()) + int((got & ~present).sum())
        num["rows_missing"] += int((present & ~got).sum())
        # first delivered row of each key (a duplicate is already counted)
        rows = np.flatnonzero(good)
        first[pos_good[::-1]] = rows[::-1]
        both = np.flatnonzero(present & got)
        at = first[both]
        self.rows_compared += both.size
        for name, kind in self.fields.items():
            out = cols[name][at]
            want = ref[kind][both]
            if kind == "count":
                num["count_mismatch"] += int((out != want).sum())
            elif kind in ("min", "max"):
                num["minmax_mismatch"] += int(
                    (out.astype(np.float32) != want).sum())
            else:
                gap = np.abs(out.astype(np.float64) - want) \
                    / np.maximum(np.abs(want), 1.0)
                gap = np.where(np.isfinite(gap), gap, np.inf)
                if gap.size:
                    num["sum_rel_gap"] = max(num["sum_rel_gap"],
                                             float(gap.max()))

    def leftovers(self, groups: dict) -> None:
        """Delivered windows the reference never closed."""
        for parts in groups.values():
            self.numbers["rows_unexpected"] += sum(p["k"].size for p in parts)


def walk_windows(stream, reference, sent, per_window, fold=None) -> None:
    """Regenerate the `sent` batches in order, fold them into `reference`,
    and call `per_window(end_ms, ref)` for each window once no later batch
    can change it, oldest first."""
    slide, panes = stream.slide_ms, stream.panes
    fold = fold or (lambda b, kidx, v, ts: reference.add(kidx, v, ts))
    done = None                         # last pane already closed

    def close_through(pane):
        nonlocal done
        ids = reference.pane_ids()
        if not ids:
            return
        start = ids[0] if done is None else done + 1
        for p in range(start, pane + 1):
            per_window((p + 1) * slide, reference.window((p + 1) * slide))
            reference.drop_before(p - panes + 2)
            done = p

    for b in sent:
        kidx, v, ts = stream.columns(b)
        close_through(int(ts[0]) // slide - 1)
        fold(b, kidx, v, ts)
    ids = reference.pane_ids()
    if ids:
        close_through(ids[-1] + panes - 1)


def compare(stream, reference, fields, sent, delivered) -> Comparison:
    """`delivered` is the sink's list of (stamp, columns)."""
    groups = group_by_window(delivered)
    cmp = Comparison(stream, fields)
    walk_windows(stream, reference, sent,
                 lambda end, ref: cmp.window(end, ref, groups.pop(end, [])))
    cmp.leftovers(groups)
    return cmp


def verdict(numbers: dict, limits: dict):
    """({name: {"value", "limit"}}, correct).  A number without a limit in
    the cell's file is an error, not a pass."""
    out = {name: {"value": value, "limit": limits[name]}
           for name, value in numbers.items()}
    return out, all(v["value"] <= v["limit"] for v in out.values())


# --------------------------------------------------------------------------
# the control: the reference in the program's place, lowered or broken
# --------------------------------------------------------------------------

def _bf16(x):
    from ml_dtypes import bfloat16

    return np.asarray(x, np.float32).astype(bfloat16).astype(np.float32)


def control_rows(stream, reference, fields, sent, mode: str, pick: int):
    """What a job would deliver if it were the reference with `mode`
    applied, as the sink's list of (stamp, columns):

    exact   nothing changed (the comparison has to pass)
    bf16    values taken and results given in bfloat16, the precision below
            the float32 the configuration states (the most generous form:
            accumulation itself stays exact)
    replay  batch `pick` folded twice: at-least-once where the
            configuration states exactly-once
    drop    batch `pick` never folded: at-most-once
    """
    if mode not in ("exact", "bf16", "replay", "drop"):
        raise ValueError(f"unknown control {mode!r}")

    def fold(b, kidx, v, ts):
        if mode == "bf16":
            v = _bf16(v)
        if b == pick and mode == "drop":
            return
        reference.add(kidx, v, ts, weight=2 if b == pick and mode == "replay"
                      else 1)

    delivered = []

    def emit(end, ref):
        if ref is None:
            return
        at = np.flatnonzero(ref["count"] > 0)
        cols = {"k": stream.universe[at],
                "window_start": np.full(at.size, end - stream.size_ms),
                "window_end": np.full(at.size, end)}
        for name, kind in fields.items():
            col = ref[kind][at]
            if kind != "count":
                col = col.astype(np.float32)
                if mode == "bf16":
                    col = _bf16(col)
            cols[name] = col
        delivered.append((0.0, cols))

    walk_windows(stream, reference, sent, emit, fold)
    return delivered
