"""Set-up seen from inside the program: the events of jax's compile path
that ENDED BEFORE THE WINDOW OPENED, from the program's own tally
(``ddlbench_tpu/telemetry/compiles.py``, installed by
``distributed.enable_compilation_cache``). What compiles after the window
(the reference flow, ``scopes.step_hlo``) does not count.

The window opened at ``T_PROCESS_START + setup_s`` of the module that
defines the run's context, and both are ``time.perf_counter`` seconds: the
tally's clock. ``seconds``: kinds whose events' seconds are summed (the
union of their intervals); ``count``: the kind whose events are counted. A
program without the tally, or one whose tally let events go, reads
nothing."""

import sys


def read(ctx, seconds=(), count=None):
    try:
        from ddlbench_tpu.telemetry import compiles
    except ImportError:
        return None
    tally = compiles.installed()
    start = getattr(sys.modules.get(type(ctx).__module__),
                    "T_PROCESS_START", None)
    if tally is None or tally.dropped or start is None \
            or ctx.setup_s is None:
        return None
    opened_ns = int((start + ctx.setup_s) * 1e9)
    if count is not None:
        return float(tally.count(count, until_ns=opened_ns))
    return tally.seconds(seconds, until_ns=opened_ns)
