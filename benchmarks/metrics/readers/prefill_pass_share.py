"""Wall time of the engine steps that ran a prefill chunk over the window,
in percent (the benchmark's spans around ``step``)."""


def read(ctx):
    return 100.0 * ctx.counters["prefill_step_wall_s"] / ctx.window_s
