"""source -> timestamps/watermarks -> key_by -> window -> aggregate -> sink,
through the public DataStream API.  The assigner and the aggregate come from
the configuration file, so a deployment that differs only in window sizes,
fields or key count is a new data file and no new code."""

from __future__ import annotations

import jax.numpy as jnp

from flink_tpu.core.functions import (CountAggregator, MaxAggregator,
                                      MinAggregator, SumAggregator,
                                      TupleAggregator)
from flink_tpu.windowing.assigners import (SlidingEventTimeWindows,
                                           TumblingEventTimeWindows)

_FIELD = {
    "sum": lambda: SumAggregator(jnp.float32),
    "count": CountAggregator,
    "min": lambda: MinAggregator(jnp.float32),
    "max": lambda: MaxAggregator(jnp.float32),
}


def output_fields(config: dict) -> dict:
    """{output column: kind} of the rows this job delivers."""
    agg = config["aggregate"]
    if agg["kind"] == "sum":
        return {"result": "sum"}
    return dict(agg["fields"])


def build(env, source, sink, config: dict) -> None:
    assigner = config["assigner"]
    if assigner["kind"] == "tumbling":
        windows = TumblingEventTimeWindows.of(assigner["size_ms"])
    elif assigner["kind"] == "sliding":
        windows = SlidingEventTimeWindows.of(assigner["size_ms"],
                                             assigner["slide_ms"])
    else:
        raise ValueError(f"unknown assigner {assigner['kind']!r}")
    agg = config["aggregate"]
    if agg["kind"] == "sum":
        aggregate = dict(agg=SumAggregator(jnp.float32), value_column="v")
    elif agg["kind"] == "tuple":
        aggregate = dict(
            agg=TupleAggregator({name: ("v", _FIELD[kind]())
                                 for name, kind in agg["fields"].items()}),
            value_selector=lambda c: c)
    else:
        raise ValueError(f"unknown aggregate {agg['kind']!r}")
    (env.from_source(source, name="bench-generator")
        .assign_timestamps_and_watermarks(
            config["guarantees"]["watermark_out_of_orderness_ms"],
            timestamp_column="ts")
        .key_by("k")
        .window(windows)
        .aggregate(**aggregate, **config["agg_options"])
        .add_sink(sink, name="bench-sink"))
