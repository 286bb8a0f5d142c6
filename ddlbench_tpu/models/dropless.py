"""The dropless expert layer of one chip's share: token-slots that a router
has already given an expert (``idx``) and a weight (``w``) are sorted by
expert, those of the experts held here gathered into a row buffer,
multiplied group by group (``grouped_dot``) through each expert's gated MLP
(``act``: SiLU unless the family says otherwise) and scattered back weighted.
The families that route their own way (models/kanana2.py: sigmoid top-6 by
one matmul; models/zaya.py: top-1 by an MLP router; models/smallthinker.py:
softmax top-6 by one matmul on the layer's input, ReLU-gated experts) share
everything after the choice.

The buffer has room for ``ROW_SLACK`` x the balanced number of held slots;
the slots of a step whose router sends more go through a second buffer, with
room for all the rest, in the taken branch of a ``lax.cond`` (its other
branch hands the sum through), so no token is ever dropped and the common
step pays for the small buffer only.
"""

from __future__ import annotations

import math
import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ddlbench_tpu.telemetry import scopes

# <family>[-l<layers kept>][-e<experts held>[r<rank>]]: the arch string that
# carries the share one chip holds
_ARCH = re.compile(r"^(?P<base>[a-z0-9_]+?)(?:-l(?P<layers>\d+))?"
                   r"(?:-e(?P<held>\d+)(?:r(?P<rank>\d+))?)?$")


def arch_base(arch: str) -> Optional[str]:
    """The family name an arch string of that syntax starts with."""
    m = _ARCH.match(arch)
    return m["base"] if m else None


def parse_share(arch: str, family: dict, min_layers: int = 1
                ) -> Optional[Tuple[object, int, Tuple[int, int]]]:
    """``(dims, layers kept, (first held expert, experts held))`` of an arch
    string whose base names an entry of ``family`` (its published sizes, with
    ``n_layers`` and ``n_experts``), None for any other; the one reader of
    that syntax. A cut the family cannot make raises."""
    m = _ARCH.match(arch)
    if m is None or m["base"] not in family:
        return None
    dims = family[m["base"]]
    layers = int(m["layers"] or dims.n_layers)
    count = int(m["held"] or dims.n_experts)
    rank = int(m["rank"] or 0)
    if not min_layers <= layers <= dims.n_layers:
        raise ValueError(f"{arch}: keeps {layers} layers of {dims.n_layers}")
    if count < 1 or dims.n_experts % count or \
            (rank + 1) * count > dims.n_experts:
        raise ValueError(
            f"{arch}: a share holds n_experts / chips experts "
            f"({dims.n_experts} experts, {count} asked for, rank {rank})")
    return dims, layers, (rank * count, count)


# rows of the grouped products' buffer, over the balanced count of held slots
ROW_SLACK = 2.0
ROW_ALIGN = 512


def buffer_rows(slots: int, n_experts: int, held: int) -> int:
    """Rows of the common step's buffer: ROW_SLACK x the balanced count of
    held slots, aligned, and never more than every slot."""
    rows = ROW_SLACK * slots * held / n_experts
    rows = int(math.ceil(rows / ROW_ALIGN) * ROW_ALIGN)
    return min(rows, slots)


def grouped_dot(a, w, sizes, tiling: Tuple[int, int, int],
                interpret: bool = False):
    """``a[rows of group g] @ w[g]`` for runs of rows ``sizes`` [G] long:
    a [M, k], w [G, k, n] -> [M, n]; rows past ``sum(sizes)`` are left
    undefined. On TPU the Pallas grouped product of
    ``jax.experimental.pallas.ops.tpu.megablox`` at the caller's (rows,
    contraction, columns) ``tiling`` (``interpret``: the same kernel off
    the chip, for tests), elsewhere XLA's ``lax.ragged_dot``.
    Why not ``ragged_dot`` on the chip too: XLA:TPU rewrites it into a
    custom call (``ragged-dot-none``) that drops the scope it was traced
    under, so its device time would read as unscoped; and on one v5e, 8
    experts x [2048, 768], 12,288 rows of which 6,144 held, forward +
    backward of the SwiGLU, a host clock (about a millisecond of dispatch
    in both) read 2.34 ms for this kernel against 3.63 (PERF.md, PR 27)."""
    from ddlbench_tpu.distributed import is_tpu_backend

    if interpret or is_tpu_backend():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(a, w, sizes, a.dtype, tiling, interpret=interpret)
    return lax.ragged_dot(a, w, sizes, preferred_element_type=a.dtype)


def _grouped_glu(pe, rows, sizes, tiling, act):
    """Each held expert's gated MLP, W_down(act(W_gate h) * W_up h), over
    its run of ``rows`` [M, d] (``sizes``: rows per expert). The grouped
    products stop at ``sum(sizes)``: what they leave in the rows past it is
    undefined, so those rows come out nought here, and go in nought so that
    no gradient comes back through them."""
    with scopes.scope(scopes.EXPERTS):
        live = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
        rows = jnp.where(live, rows, 0)
        dot = lambda a, w: grouped_dot(a, w.astype(a.dtype), sizes, tiling)
        g = dot(rows, pe["w_gate"])
        u = dot(rows, pe["w_up"])
        return jnp.where(live, dot(act(g) * u, pe["w_down"]), 0)


def routed_experts(pe, h, idx, w, held: Tuple[int, int], n_experts: int,
                   tiling: Tuple[int, int, int], act=jax.nn.silu):
    """The held experts' part of ``sum_k w_k E_idx_k(h)`` for h [S, d],
    ``idx`` [S, k] int32 and ``w`` [S, k] float32 as the family's router
    gave them, ``pe`` the held experts' stacked gate, up and down weights,
    ``act`` the gate's activation, and the layer's counters. Deterministic
    (a stable sort): a rematerialized forward routes as the first one
    did."""
    S, d = h.shape
    k = idx.shape[1]
    first, count = held
    local = idx.reshape(-1) - first  # [S * k]
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count)  # absent experts sort to the end
    order = jnp.argsort(key, stable=True)  # held slots first, by expert
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    n_held = ends[-1]
    w_flat = jnp.where(mine, w.reshape(-1), 0.0)

    def through(lo: int, rows: int):
        """acc + the weighted outputs of sorted slots [lo, lo + rows)."""
        def f(acc, h, w_flat, pe):
            slot = order[lo:lo + rows]
            token = slot // k
            # each expert's run, cut to this window of the sorted order
            cut = lambda x: jnp.clip(x, lo, lo + rows)
            y = _grouped_glu(pe, jnp.take(h, token, axis=0),
                             cut(ends) - cut(ends - sizes), tiling, act)
            y = y.astype(jnp.float32) * jnp.take(w_flat, slot)[:, None]
            return acc.at[token].add(y)
        return f

    # the common buffer always; the slots past it, if a step has any, in the
    # second branch of a cond that otherwise hands the sum through (all the
    # hot work stays outside the conditional, under its own names)
    small = buffer_rows(S * k, n_experts, count)
    acc = through(0, small)(jnp.zeros((S, d), jnp.float32), h, w_flat, pe)
    if small < S * k:
        # rematerialized in the backward pass: a cond hands every residual
        # of either branch out of both, so the other branch would fill the
        # large buffers' residuals with zeros on every step (measured: 3.3
        # ms a layer, PERF.md PR 27)
        acc = lax.cond(n_held > small,
                       jax.checkpoint(through(small, S * k - small)),
                       lambda acc, *_: acc, acc, h, w_flat, pe)
    y = acc.astype(h.dtype)
    # load over ALL experts, as the router sees it (the held ones are a
    # sample of it): the fullest expert's slots over the mean expert's
    load = jnp.sum(jax.nn.one_hot(idx.reshape(-1), n_experts,
                                  dtype=jnp.float32), axis=0)
    counters = {"held_slots": n_held.astype(jnp.float32),
                "load_max_over_mean": jnp.max(load) * n_experts / (S * k)}
    return y, counters
