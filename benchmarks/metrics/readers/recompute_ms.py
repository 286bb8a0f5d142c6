"""Device time per step of the RECOMPUTED forward: 1000 x the summed device
seconds of the traced instructions on whose op_name path the token
``rematted_computation`` stands, over the window's steps. jax puts that
token on every instruction it rematerializes under a ``jax.checkpoint``
(``.../transpose(jvp(block1))/jvp(block1)/checkpoint/rematted_computation/
ln/mul``); the backward proper of a checkpointed layer carries
``checkpoint`` without it, and what the checkpoint's policy keeps from the
forward pass is not recomputed and so not counted. The token is jax's and
no scope of the program's, so a scope-less program reads too. The join is
``scope_ms``'s (the compiled step's {instruction: op_name} onto the trace's
seconds by instruction name; a fusion goes whole to its root's path: a
recomputed op that XLA fused under a backward root counts with the
backward). No instruction matched (a step that rematerializes nothing)
returns nothing, never 0."""

from benchmarks.harness import scopes
from benchmarks.metrics.readers.scope_part_ms import innermost

TOKEN = "rematted_computation"


def recomputed(op_name):
    """Whether the first path of ``op_name`` holds the token as a scope of
    its own (``scope_part_ms``'s rule for a part's name)."""
    return innermost(op_name, (TOKEN,)) is not None


def read(ctx):
    table = scopes.scope_table(scopes.step_hlo(ctx))
    times = [s for name, s in ctx.trace_summary.op_seconds.items()
             if recomputed(table.get(name, ""))]
    if not times:
        return None
    return 1000.0 * sum(times) / ctx.counters["steps"]
