"""Share of the traced window in which a chip had a collective in flight
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all:
``harness/trace.py``) and ran no other operation: the communication that the
step does not hide, on the chip that shows most of it, in percent. A window
without collectives (one chip) returns nothing, never 0."""


def read(ctx):
    ts = ctx.trace_summary
    if ts.collective_s <= 0.0:
        return None
    return 100.0 * ts.collective_exposed_s / ts.window_s
