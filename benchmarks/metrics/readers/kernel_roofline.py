"""A kernel's share of its roofline over the traced window: the least time
the chip could take for the calls' work — max(FLOPs / peak, bytes / HBM
bandwidth), work from ``benchmarks/kernels/<kernel>.py`` — over the summed
device time of the kernel's trace events. Nothing to read (the kernel is off
the path) returns nothing, never 0."""

import sys


def read(ctx, kernel):
    mod = ctx.manifest.kernel(kernel)
    seconds = ctx.trace_summary.kernel_seconds(mod.EVENTS)
    if seconds <= 0.0:
        return None
    flops, nbytes = mod.calls(ctx)
    t_flops = flops / ctx.peaks.flops_bf16
    t_bytes = nbytes / ctx.peaks.hbm_bytes_per_s
    print(f"{kernel}: {seconds:.4f}s on device; least {t_flops:.4f}s by "
          f"FLOPs, {t_bytes:.4f}s by bytes "
          f"({'compute' if t_flops >= t_bytes else 'bandwidth'}-bound)",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / seconds
