"""The whole step's share of the chips' bf16 peak: model FLOPs of the work
completed in the window (counted by the configuration's reference module,
from shapes, no recompute) over window seconds, chips and
``harness/peaks.py``."""


def read(ctx):
    rate = ctx.counters["model_flops"] / ctx.window_s
    return 100.0 * rate / (ctx.chips * ctx.peaks.flops_bf16)
