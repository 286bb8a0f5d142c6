"""The dropless expert layer of one chip's share: token-slots that a router
has already given an expert (``idx``) and a weight (``w``) are sorted by
expert, those of the experts held here gathered into a row buffer,
multiplied group by group (``grouped_dot``) through each expert's gated MLP
(``act``: SiLU unless the family says otherwise) and summed back weighted.
The families that route their own way (models/kanana2.py: sigmoid top-6 by
one matmul; models/zaya.py: top-1 by an MLP router; models/smallthinker.py:
softmax top-6 by one matmul on the layer's input, ReLU-gated experts) share
everything after the choice.

The buffer has room for ``ROW_SLACK`` x the balanced number of held slots;
the slots of a step whose router sends more go through a second buffer, with
room for all the rest, in the taken branch of a ``lax.cond`` (its other
branch hands the sum through), so no token is ever dropped and the common
step pays for the small buffer only.

The common buffer's rows move by gathers alone (``_dispatch``,
``_combine``): XLA:TPU gathers rows fast and scatter-adds them slowly (one
v5e: 0.20 ms to gather 16,384 rows of 2,048 bf16, 2.27 ms to scatter-add
them weighted into a float32 sum; PERF.md section 6), so each token's sum
of its slots is a gather too, token-major, and the transposes are written
out so that the backward scatters nothing either. The second buffer, whose
branch no cell's router takes, keeps the gather and scatter-add.
"""

from __future__ import annotations

import functools
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


def initial_counters(*more: str) -> dict:
    """An expert layer's ``moe`` state before its first step: the counters
    ``routed_experts`` returns, and the ``more`` its family adds, nought."""
    names = ("held_slots", "load_max_over_mean", "buffer_fill") + more
    return {name: jnp.float32(0.0) for name in names}


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


def _grouped_glu(pe, rows, sizes, tiling, act, masked: bool = True):
    """Each held expert's gated MLP, W_down(act(W_gate h) * W_up h), over
    its run of ``rows`` [M, d] (``sizes``: rows per expert). The grouped
    products stop at ``sum(sizes)``: what they leave in the rows past it is
    undefined, so (``masked``) those rows come out nought here, and go in
    nought so that no gradient comes back through them. Unmasked for
    callers that never read those rows and send them a nought gradient:
    megablox's products keep each row to itself and SELECT the rows past
    the groups away where they sum over rows (``tgmm``), so nothing there,
    a NaN neither, reaches a live row or a weight (tests/test_expert_rows.py
    fills them with NaN)."""
    with scopes.scope(scopes.EXPERTS):
        dot = lambda a, w: grouped_dot(a, w.astype(a.dtype), sizes, tiling)
        if not masked:
            return dot(act(dot(rows, pe["w_gate"])) * dot(rows, pe["w_up"]),
                       pe["w_down"])
        live = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
        rows = jnp.where(live, rows, 0)
        g = dot(rows, pe["w_gate"])
        u = dot(rows, pe["w_up"])
        return jnp.where(live, dot(act(g) * u, pe["w_down"]), 0)


def _read_rows(pos, rows: int):
    """The row each slot's gather reads, [S, k]: its own where ``pos``
    names one, else row t mod ``rows`` of its token t, which the sum
    selects away. Not one row for every empty slot: on a v5e a gather whose
    indices are mostly one row takes a fifth longer than one of as many
    distinct rows (PERF.md section 6)."""
    t = jnp.arange(pos.shape[0], dtype=pos.dtype) % rows
    return jnp.where(pos >= 0, pos, t[:, None])


def _token_sum(rows, w, pos, dtype):
    """``out[t] = sum_j w[t, j] * rows[pos[t, j]]`` over the slots with a
    row (``pos`` >= 0; ``w`` None: unweighted), summed in float32: k
    gathers of S rows. A slot without a row reads a row of the buffer
    (``_read_rows``) and is selected away, so nothing in the rows it does
    not name, a NaN neither, reaches the sum."""
    read = _read_rows(pos, rows.shape[0])
    out = 0.0
    for j in range(pos.shape[1]):
        at = pos[:, j]
        x = jnp.take(rows, read[:, j], axis=0,
                     mode="clip").astype(jnp.float32)
        if w is not None:
            x = x * w[:, j:j + 1]
        out = out + jnp.where(at[:, None] >= 0, x, 0.0)
    return out.astype(dtype)


@jax.custom_vjp
def _dispatch(h, slot, pos):
    """The buffer's rows, ``h[slot // k]`` [R, d] (``slot``: the token-slots
    in buffer order; ``pos`` [S, k]: each slot's row in the buffer, -1
    without one). Its transpose sums each token's rows, token-major."""
    # in range by construction; the default mode would add a select over
    # the whole buffer for the indices past the end
    return jnp.take(h, slot // pos.shape[1], axis=0, mode="clip")


def _dispatch_fwd(h, slot, pos):
    return _dispatch(h, slot, pos), (slot, pos)


def _dispatch_bwd(res, g):
    slot, pos = res
    return _token_sum(g, None, pos, g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(rows, w, slot, pos, dtype):
    """Each token's weighted sum of its slots' ``rows``, [S, d] in
    ``dtype`` (``_token_sum``). Its transpose in ``rows`` gathers the
    token's gradient into each row, scaled by the slot's weight (a row
    without a held slot has weight nought), and in ``w`` takes the row dot
    of the two, slot-major."""
    return _token_sum(rows, w, pos, dtype)


def _combine_fwd(rows, w, slot, pos, dtype):
    return _combine(rows, w, slot, pos, dtype), (rows, w, slot, pos)


def _combine_bwd(dtype, res, g):
    rows, w, slot, pos = res
    S, k = pos.shape
    dy = jnp.take(g, slot // k, axis=0).astype(jnp.float32)
    d_rows = dy * jnp.take(w.reshape(-1), slot)[:, None]
    dw = jnp.sum(dy * rows.astype(jnp.float32), axis=1)
    at = pos.reshape(-1)
    dw = jnp.where(at >= 0, jnp.take(dw, jnp.maximum(at, 0)), 0.0)
    return d_rows.astype(rows.dtype), dw.reshape(S, k), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _route(idx, w, held: Tuple[int, int]):
    """The layer's slots sorted for the buffers: ``order`` (held slots
    first, by expert; a stable sort), each held expert's run ``sizes`` and
    their ``ends`` in that order, and the slots' weights ``w_flat``, nought
    where the slot's expert is not held."""
    first, count = held
    local = idx.reshape(-1) - first  # [S * k]
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count)  # absent experts sort to the end
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, sizes, jnp.cumsum(sizes), jnp.where(mine, w.reshape(-1),
                                                      0.0)


def _through(order, sizes, ends, lo: int, rows: int, tiling, act):
    """``f(acc, h, w_flat, pe)``: acc + the weighted outputs of the sorted
    slots [lo, lo + rows), by a gather of their tokens' rows and a
    scatter-add of the products' rows into ``acc``."""
    def f(acc, h, w_flat, pe):
        slot = order[lo:lo + rows]
        token = slot // (w_flat.shape[0] // h.shape[0])
        # each expert's run, cut to this window of the sorted order
        cut = lambda x: jnp.clip(x, lo, lo + rows)
        y = _grouped_glu(pe, jnp.take(h, token, axis=0),
                         cut(ends) - cut(ends - sizes), tiling, act)
        y = y.astype(jnp.float32) * jnp.take(w_flat, slot)[:, None]
        return acc.at[token].add(y)
    return f


def _gathered(pe, h, order, sizes, ends, n_held, w_flat, rows: int, tiling,
              act, dtype):
    """What ``_through(order, sizes, ends, 0, rows, ...)`` adds to a zero
    sum, by gathers alone, in ``dtype``."""
    S, k = h.shape[0], w_flat.shape[0] // h.shape[0]
    slot = order[:rows]
    # each slot's row in the buffer: its place in the sorted order, -1
    # where that is past the buffer or the held slots
    at = jnp.argsort(order)
    pos = jnp.where(at < jnp.minimum(n_held, rows), at, -1).reshape(S, k)
    cut = lambda v: jnp.clip(v, 0, rows)
    y = _grouped_glu(pe, _dispatch(h, slot, pos),
                     cut(ends) - cut(ends - sizes), tiling, act, masked=False)
    return _combine(y, w_flat.reshape(S, k), slot, pos, jnp.dtype(dtype))


def routed_experts(pe, h, idx, w, held: Tuple[int, int], n_experts: int,
                   tiling: Tuple[int, int, int], act=jax.nn.silu):
    """The held experts' part of ``sum_k w_k E_idx_k(h)`` for h [S, d],
    ``idx`` [S, k] int32 and ``w`` [S, k] float32 as the family's router
    gave them, ``pe`` the held experts' stacked gate, up and down weights,
    ``act`` the gate's activation, and the layer's counters. Deterministic
    (a stable sort): a rematerialized forward routes as the first one
    did. Counters: the held slots, the fullest expert's load over the mean,
    and ``buffer_fill``, the held slots over the common buffer's rows (over
    1 when the step took the second buffer)."""
    S, d = h.shape
    k = idx.shape[1]
    count = held[1]
    order, sizes, ends, w_flat = _route(idx, w, held)
    n_held = ends[-1]
    # the common buffer always, by gathers alone; the slots past it, if a
    # step has any, in the second branch of a cond that otherwise hands the
    # sum through (all the hot work stays outside the conditional, under
    # its own names), by a gather and a scatter-add
    small = buffer_rows(S * k, n_experts, count)
    # float32 where the second branch adds to it, as XLA's sum is
    acc = _gathered(pe, h, order, sizes, ends, n_held, w_flat, small, tiling,
                    act, jnp.float32 if small < S * k else h.dtype)
    if small < S * k:
        # rematerialized in the backward pass: a cond hands every residual
        # of either branch out of both, so the other branch would fill the
        # large buffers' residuals with zeros on every step (measured: 3.3
        # ms a layer, PERF.md PR 27)
        acc = lax.cond(n_held > small,
                       jax.checkpoint(_through(order, sizes, ends, small,
                                               S * k - small, tiling, act)),
                       lambda acc, *_: acc, acc, h, w_flat, pe)
    y = acc.astype(h.dtype)
    # load over ALL experts, as the router sees it (the held ones are a
    # sample of it): the fullest expert's slots over the mean expert's
    load = jnp.sum(jax.nn.one_hot(idx.reshape(-1), n_experts,
                                  dtype=jnp.float32), axis=0)
    counters = {"held_slots": n_held.astype(jnp.float32),
                "load_max_over_mean": jnp.max(load) * n_experts / (S * k),
                "buffer_fill": n_held.astype(jnp.float32) / small}
    return y, counters
