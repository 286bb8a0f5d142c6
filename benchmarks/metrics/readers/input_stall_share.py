"""Share of the window the step loop spent blocked on the ``Prefetcher``'s
ring (its own ``stall_s`` account), in percent."""


def read(ctx):
    return 100.0 * ctx.counters["input_stall_s"] / ctx.window_s
