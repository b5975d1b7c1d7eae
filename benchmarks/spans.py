#!/usr/bin/env python3
"""python3 benchmarks/spans.py --workload <cell> --seed <n> --seconds <s> [--keep DIR]

One traced run of a cell, as `run.py --trace 1` makes it, that also reports
the metrics of `span_metrics.json`: those that read the program's spans, its
new phases and its named device programs.  The accepted harness cannot
report a new metric in a cell it has without an edit to files that are
there (the cell's `per_layer` map, `readers`, `trace_reduce.reduce_dir`), and
only a benchmark PR may make those.  Until one does, this file stands in
for them from outside: it hands `runner.run_cell` the cell's list with the
new metrics appended, the new readers beside the old, and a reduction that
returns the spans beside what it returned before.  Every accepted metric is
computed from the same inputs by the same code.  `--keep DIR` copies the
slice's `.xplane.pb` there before the run deletes it, and its size is
printed either way.
"""

from __future__ import annotations

import time

STARTED = time.monotonic()

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def install(cell_name: str, keep: str = "", say=print):
    """Stand in for the three insertions: returns the names added."""
    from harness import readers, runner, span_readers, span_reduce, \
        trace_reduce

    added = runner.load_json("span_metrics.json")
    names = added["cells"][cell_name]
    accepted = runner.layer_metrics

    def layer_metrics(cell):
        return accepted(cell) + [(name, added["files"][stem])
                                 for name, stem in names.items()]

    def reduce_dir(trace_dir):
        path = span_reduce.find_xplane(trace_dir)
        say(f"traced slice: {os.path.basename(path)} "
            f"{os.path.getsize(path)} bytes")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, f"{cell_name}.xplane.pb"))
        reduced = span_reduce.reduce_dir(trace_dir)
        say("spans in the slice (seconds, count, device-idle seconds under "
            "it): " + json.dumps({k: [round(v["seconds"], 6), v["count"],
                                      round(v["idle_s"], 6)] for k, v in
                                  sorted(reduced["spans"].items())}))
        say(f"device idle {reduced['idle_s']:.6f} s of the slice, "
            f"{reduced['idle_unattributed_s']:.6f} s under no span")
        return reduced

    runner.layer_metrics = layer_metrics
    trace_reduce.reduce_dir = reduce_dir
    for reader in ("span_ms_per_mrec", "idle_share_under",
                   "idle_unattributed_share", "module_ms"):
        setattr(readers, reader, getattr(span_readers, reader))
    return list(names)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--keep", default="")
    args = p.parse_args(argv)
    import run

    install(args.workload, args.keep)
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
