"""The plain reference for `jobs/keyed_window.py`: a numpy group-by over the
regenerated events.  Imports nothing of the program.

Events fold into per-slide panes (f64 sum, exact count, f32 min and max);
a window is the combine of its panes.  Panes are dropped once no window
needs them, so memory is bounded by one window's panes whatever the run's
length."""

from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, config: dict):
        assigner = config["assigner"]
        self.size_ms = int(assigner["size_ms"])
        self.slide_ms = int(assigner.get("slide_ms", self.size_ms))
        self.n_keys = int(config["keys"]["count"])
        agg = config["aggregate"]
        self._kinds = {"count"} | ({"sum"} if agg["kind"] == "sum"
                                   else set(agg["fields"].values()))
        self._panes = {}

    def _pane(self, p: int) -> dict:
        pane = self._panes.get(p)
        if pane is None:
            pane = {"count": np.zeros(self.n_keys, np.int64)}
            if "sum" in self._kinds:
                pane["sum"] = np.zeros(self.n_keys, np.float64)
            if "min" in self._kinds:
                pane["min"] = np.full(self.n_keys, np.inf, np.float32)
            if "max" in self._kinds:
                pane["max"] = np.full(self.n_keys, -np.inf, np.float32)
            self._panes[p] = pane
        return pane

    def add(self, kidx, v, ts, weight: int = 1) -> None:
        """Fold one batch; `weight` 2 folds it twice (the replay control)."""
        pane_of = ts // self.slide_ms
        for p in np.unique(pane_of).tolist():
            sel = pane_of == p
            k, x = kidx[sel], v[sel]
            pane = self._pane(p)
            pane["count"] += weight * np.bincount(k, minlength=self.n_keys)
            if "sum" in pane:
                pane["sum"] += weight * np.bincount(
                    k, weights=x.astype(np.float64), minlength=self.n_keys)
            if "min" in pane or "max" in pane:
                order = np.argsort(k, kind="stable")
                ks, xs = k[order], x[order]
                starts = np.flatnonzero(np.r_[True, ks[1:] != ks[:-1]])
                u = ks[starts]          # distinct, so plain indexing folds
                if "min" in pane:
                    pane["min"][u] = np.minimum(
                        pane["min"][u], np.minimum.reduceat(xs, starts))
                if "max" in pane:
                    pane["max"][u] = np.maximum(
                        pane["max"][u], np.maximum.reduceat(xs, starts))

    def pane_ids(self):
        return sorted(self._panes)

    def window(self, end_ms: int) -> dict:
        """{kind: array over the universe} of the window ending at `end_ms`;
        `count` 0 marks a key with no row."""
        last = end_ms // self.slide_ms - 1
        first = last - self.size_ms // self.slide_ms + 1
        out = None
        for p in range(first, last + 1):
            pane = self._panes.get(p)
            if pane is None:
                continue
            if out is None:
                out = {k: a.copy() for k, a in pane.items()}
                continue
            out["count"] += pane["count"]
            if "sum" in out:
                out["sum"] += pane["sum"]
            if "min" in out:
                np.minimum(out["min"], pane["min"], out=out["min"])
            if "max" in out:
                np.maximum(out["max"], pane["max"], out=out["max"])
        return out

    def drop_before(self, pane: int) -> None:
        for p in [p for p in self._panes if p < pane]:
            del self._panes[p]
