"""K1's share of its roofline (%): the least time the chip could take for
the tiles K1 visited in the traced steps (``bench/work/k1.py``: the larger
of their operations over the VPU's peak and their bytes over HBM's) over
the device time of K1's own launches in those steps (the ops of the ``k1``
scope that name the kernel, not those that pack and unpack its operands). The tiles are
``k1_tiles`` summed over the run's checked steps
(``repro.core.telemetry.step_totals``), which in a traced run are the
traced ones. None for a program without the counter or the scope, or a
device the peaks table does not hold."""

from bench import subphases
from bench.work import k1


def read(ctx):
    try:
        from repro.core import telemetry
        totals = telemetry.step_totals()
    except (ImportError, AttributeError):
        return None
    if totals is None or totals.counts.get("k1_tiles", 0) <= 0:
        return None
    seconds = subphases.kernel_s(ctx.reduction, "k1", k1.KERNEL)
    if seconds <= 0 or ctx.device_kind not in k1.PEAKS:
        return None
    least = k1.least_seconds(totals.counts["k1_tiles"],
                             totals.counts["k1_live_rows"], ctx.device_kind)
    return 100.0 * least / seconds
