"""Share of K1's column-map width that the last checked step's neediest row
block filled (%): ``colmap_demand`` (``repro.core.telemetry.last_step``)
over the configuration's ``colmap_width``. None for a program that keeps
no such counter, or a configuration that leaves the width to the engine."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    step = telemetry.last_step()
    width = ctx.params.get("engine", {}).get("colmap_width")
    if step is None or not width or step.counts.get("colmap_demand", 0) <= 0:
        return None
    return 100.0 * step.counts["colmap_demand"] / width
