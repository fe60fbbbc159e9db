"""Share of the fused sweep's query rows that its window path served (%):
``sweep_window_rows / sweep_rows`` of the last step that the run loop
checked (``repro.core.telemetry.last_step``), each summed over shards or
lanes. None for a program that keeps no such counters."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    step = telemetry.last_step()
    if step is None or step.counts.get("sweep_rows", 0) <= 0 \
            or "sweep_window_rows" not in step.counts:
        return None
    return 100.0 * step.counts["sweep_window_rows"] / step.counts["sweep_rows"]
