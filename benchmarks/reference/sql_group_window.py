"""The plain reference for `jobs/sql_group_window.py`: what the statement
means, as a numpy group-by over the regenerated events.  Imports nothing of
the program.

Derived from `reference/keyed_window.py`, as `keyed_window_mesh.py` is,
and not written out again: SUM, COUNT(*), MIN and MAX over a TUMBLE or HOP
group window are that class's per-pane f64 sums, exact int64 counts and f32
min / max, letter for letter (it reads the kinds from the configuration's
accumulators), and a second copy of the pane walk could only drift from the
one `compare.walk_windows` was written against.  What SQL adds is AVG, which
is no accumulator of its own: `avg` = the window's f64 sum over its exact
count, taken here once per window and never per pane."""

from __future__ import annotations

import numpy as np

from reference.keyed_window import Reference as _GroupBy


class Reference(_GroupBy):
    def window(self, end_ms: int) -> dict:
        """`keyed_window.Reference.window` plus `avg`; a key with no row
        (`count` 0) reads 0 there and is never compared."""
        out = super().window(end_ms)
        if out is not None:
            out["avg"] = out["sum"] / np.maximum(out["count"], 1)
        return out
