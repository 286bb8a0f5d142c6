"""Fullest chip's peak after the window over the chip's HBM: the
allocator's ``peak_bytes_in_use`` plus ``peak_bytes_reserved``, the programs'
scratch, which the first leaves out (``RunContext.read_memory_peak``). It is
what a configuration has to fit into (PERF.md section 6 gives both
readings)."""


def read(ctx):
    return 100.0 * ctx.memory_peak_bytes / ctx.peaks.hbm_bytes
