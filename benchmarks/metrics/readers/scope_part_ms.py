"""Device time per step under a named PART of a layer's work that is no kind
of the scope vocabulary (``ddlbench_tpu/telemetry/scopes.py`` PARTS: a
model's own scopes inside a layer instance, such as ``latent``, ``route``,
``experts``): 1000 x the summed device seconds of the traced instructions on
whose op_name path ``part`` is the innermost token among the vocabulary's
kinds and ``parts``, forward and backward together, over the window's steps.

Why not ``scope_ms``: the kinds are the ``scope_kinds*.json`` lists, and an
accepted test (tests/benchmark/test_scope_readers.py) holds the committed
vocabulary to its ten, so a PR that may only add cannot add a kind. The join
is ``scope_ms``'s (the compiled step's {instruction: op_name} onto the
trace's seconds by instruction name; a fusion goes whole to its root's
scope). A program without the part (the parent of the PR that adds it)
returns nothing, never 0."""

import functools
import re

from benchmarks.harness import scopes


@functools.lru_cache(maxsize=None)
def _standing_alone(tokens):
    return re.compile(r"(?<![\w.\-])(" + "|".join(map(re.escape, tokens))
                      + r")(?![\w.\-])")


def innermost(op_name, tokens):
    """The last of ``tokens`` (a tuple) that stands on the path as a scope
    of its own (``jvp(block2)/route/cond/branch_1_fun/experts/jit(gmm)`` ->
    experts)."""
    found = _standing_alone(tokens).findall(op_name.split(";", 1)[0])
    return found[-1] if found else None


def read(ctx, part, parts):
    if scopes.device_time(ctx) is None:
        return None
    tokens = tuple(scopes.KINDS) + tuple(parts)
    table = scopes.scope_table(scopes.step_hlo(ctx))
    seconds, n = 0.0, 0
    for name, s in ctx.trace_summary.op_seconds.items():
        if innermost(table.get(name, ""), tokens) == part:
            seconds, n = seconds + s, n + 1
    if n == 0:
        return None
    return 1000.0 * seconds / ctx.counters["steps"]
