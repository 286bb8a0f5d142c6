"""The guard of the scope metrics: 100 x the device seconds of traced
instructions that carry none of the program's named scopes, or that the
compiled step's table does not hold, over all device seconds of the window.
A refactor that drops a scope, or a trace whose instruction names no longer
match the step's, shows here. A program without any scope returns nothing
(``harness/scopes.py``)."""

from benchmarks.harness import scopes


def read(ctx):
    times = scopes.device_time(ctx)
    if times is None or times.total_s <= 0.0:
        return None
    return 100.0 * sum(s for _, s in times.unscoped) / times.total_s
