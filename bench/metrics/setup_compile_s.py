"""Seconds the run's process spent tracing, lowering and compiling or
loading compiled programs by the last step that the run loop checked
(``repro.core.telemetry.last_step``): set-up's, since the window runs what
set-up compiled, and without the reference check's compiles after it. None
for a program that does not count them."""


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    step = telemetry.last_step()
    if step is None or step.compile_s <= 0:
        return None
    return step.compile_s
