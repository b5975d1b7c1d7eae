"""Per-layer readers of the program's spans and named device programs, over
`ctx.trace` as `span_reduce.reduce_dir` leaves it.  Each takes the `Context`
of `readers` first, like the readers there.  A metric is listed only for
cells whose traced slice holds its span or module, so a trace without it is
an error and not a silent gap: a refactor that drops a span fails the traced
run, as `readers.module_roofline` does for its module."""

from __future__ import annotations

from . import span_reduce


def _span(ctx, name: str) -> dict:
    found = ctx.trace.get("spans", {}).get(name)
    if not found or not found["count"]:
        raise RuntimeError(
            f"no span {name!r} in the traced slice; it holds "
            f"{sorted(ctx.trace.get('spans', {}))}")
    return found


def span_ms_per_mrec(ctx, span, role):
    """The span's time in the traced slice, ms per million records the
    `role` (source | window) tasks took in over the slice."""
    seconds = _span(ctx, span)["seconds"]
    records = ctx.delta(role, "records_in", ("trace0", "trace1"))
    return seconds * 1e3 / (records / 1e6) if records else None


def idle_share_under(ctx, spans):
    """Of the slice's device-idle time, the share during which one of
    `spans` was the innermost span of some host thread, %."""
    for name in spans:
        _span(ctx, name)
    idle = ctx.trace["idle_s"]
    return 100.0 * span_reduce.idle_under(ctx.trace, spans) / idle \
        if idle else None


def idle_unattributed_share(ctx):
    """Of the slice's device-idle time, the share with no program or
    harness span open on any host thread, %."""
    if not ctx.trace.get("spans"):
        raise RuntimeError("the traced slice holds no program span at all")
    idle = ctx.trace["idle_s"]
    return 100.0 * ctx.trace["idle_unattributed_s"] / idle if idle else None


def module_ms(ctx, module, per):
    """Device time of the traced `module` in the slice, ms per occurrence of
    the span `per` on one subtask (`window_agg.snapshot_d2h`: per cut)."""
    found = ctx.trace["modules"].get(module)
    if not found or not found["seconds"]:
        raise RuntimeError(
            f"no device time of module {module!r} in the trace; it holds "
            f"{sorted(ctx.trace['modules'])}")
    times = _span(ctx, per)["count"] / ctx.config["parallelism"]
    return found["seconds"] * 1e3 / times
