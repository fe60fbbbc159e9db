"""Share of the fused sweep's gathered slots that held a live candidate
(%): ``sweep_candidates / sweep_slots`` of the last step that the run loop
checked (``repro.core.telemetry.last_step``), each summed over shards or
lanes. None for a program that keeps no such counters."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    step = telemetry.last_step()
    if step is None or step.counts.get("sweep_slots", 0) <= 0:
        return None
    return 100.0 * step.counts["sweep_candidates"] / step.counts["sweep_slots"]
