#!/usr/bin/env python3
"""python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--slides 3]

The control of the comparison, at the cell's own size: the reference put in
the program's place with one thing lowered or broken (`compare.control_rows`),
judged by the same comparison and limits as a run.  Every mode has to come out
as not correct; `exact` has to pass.  The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--slides", type=int, default=3,
                   help="measured slides after the warm-up")
    args = p.parse_args(argv)
    from harness import compare, runner
    from harness.generator import Stream

    cell, config, traffic = runner.load_cell(args.workload)
    job = importlib.import_module(f"jobs.{config['job']}")
    reference = importlib.import_module(f"reference.{config['job']}")
    fields = job.output_fields(config)
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        stream = Stream(config, traffic, seed)
        sent = list(range(stream.warm_batches
                          + args.slides * stream.batches_per_slide))
        pick = stream.warm_batches + seed % stream.batches_per_slide
        for mode in ("exact", "bf16", "replay", "drop"):
            rows = compare.control_rows(stream, reference.Reference(config),
                                        fields, sent, mode, pick)
            result = compare.compare(stream, reference.Reference(config),
                                     fields, sent, rows)
            numbers, correct = compare.verdict(result.numbers, cell["limits"])
            as_expected &= correct == (mode == "exact")
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": mode, "correct": correct,
                              "rows_compared": result.rows_compared,
                              "compared": numbers}), flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
