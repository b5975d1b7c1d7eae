#!/usr/bin/env python3
"""python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json, in one process with no children.
Exits non-zero with no result line when JAX finds no TPU (or fewer chips
than the cell asks for), when the native library did not build, or when the
program is not in the checkout.  The last line of standard output is the
result; lanes, byte counts and walls go on earlier lines, and the numbers
compared, each beside its limit, are the last lines of standard error.
"""

from __future__ import annotations

import time

STARTED = time.monotonic()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        from flink_tpu.utils.platform import configure_compile_cache
    except ImportError as err:
        print(f"benchmark: the program is not in this checkout: {err}",
              file=sys.stderr)
        return 4
    cache_dir = configure_compile_cache()
    from importlib import metadata

    import jax

    from harness import runner

    cell = runner.load_json("workloads", f"{args.workload}.json")
    print(" ".join(f"{pkg} {metadata.version(pkg)}"
                   for pkg in ("jax", "jaxlib", "libtpu")))
    devices = jax.devices()
    print(f"platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind} count={len(devices)}")
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmark: cell needs {cell['chips']} TPU chip(s), found "
              f"{len(devices)} x {devices[0].platform!r}", file=sys.stderr)
        return 2
    from flink_tpu import native

    if not native.native_available():
        print(f"benchmark: native layer did not build: "
              f"{native.build_error()}", file=sys.stderr)
        return 3
    line = runner.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), STARTED, cache_dir)
    sys.stdout.flush()
    for name, pair in line["compared"].items():
        print(f"compared {name}: {pair['value']} limit {pair['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
