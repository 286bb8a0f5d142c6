"""1 - (union of device-op intervals / traced window), mean over devices."""


def read(ctx):
    ts = ctx.trace_summary
    return 100.0 * (1.0 - ts.busy_s / ts.window_s)
