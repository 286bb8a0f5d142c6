"""Device time per step under one of the program's named scopes: 1000 x the
summed device seconds of the traced instructions that the compiled step's
op_names put in ``phase`` (forward, backward, optimizer, grad_sync) or in
one of ``kinds`` (conv, bn, ln, attn, ...; forward and backward together),
over the window's steps. The join and its limits (a fusion goes to its
root's scope; names shared between programs) are in ``harness/scopes.py``.
No instruction matched (a program without scopes, a kind the model lacks)
returns nothing, never 0."""

from benchmarks.harness import scopes


def read(ctx, phase=None, kinds=None):
    times = scopes.device_time(ctx)
    if times is None:
        return None
    seconds, n = times.seconds(phase, kinds)
    if n == 0:
        return None
    return 1000.0 * seconds / ctx.counters["steps"]
