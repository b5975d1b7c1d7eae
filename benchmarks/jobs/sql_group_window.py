"""A SQL group-window aggregate, planned by `flink_tpu/sql` and sharded over
the chips of one host: the generator is registered as the table
`lineitem(k, v, ts)` with rowtime `ts`, the configuration's statement goes
through `TableEnvironment.sql_query` (parser, planner, rules, the planner's
physical operators) and its result stream gets the sink.  No operator is
built by hand and no aggregate option is passed: `env.set_mesh` is all that
says where the keyed state lives."""

from __future__ import annotations

from flink_tpu.sql.table_env import TableEnvironment

#: output column -> the kind the comparison holds it to (`avg` is compared
#: as a sum is: to a relative gap)
_OUTPUT = {"total": "sum", "n": "count", "lo": "min", "hi": "max",
           "mean": "avg"}


def output_fields(config: dict) -> dict:
    """{output column: kind} of the rows this job delivers."""
    return dict(_OUTPUT)


def build(env, source, sink, config: dict) -> None:
    env.set_mesh(n_devices=config["mesh_chips"]["here"])
    tenv = TableEnvironment(parallelism=config["parallelism"])
    tenv.register_source(
        "lineitem", source, ["k", "v", "ts"], rowtime="ts",
        watermark_delay_ms=config["guarantees"][
            "watermark_out_of_orderness_ms"])
    (tenv.sql_query(config["sql"])
        .to_data_stream(env)
        .add_sink(sink, name="bench-sink"))
