"""Programs that jax compiled, or loaded from its cache, inside the window
(``/jax/core/compile/backend_compile_duration`` events). Expect 0: every
shape is warmed in set-up."""


def read(ctx):
    return float(ctx.counters["window_compiles"])
