"""The one general traffic generator. A traffic mix is a data file under
``benchmarks/traffic/``; nothing here knows a cell by name.

Serving mixes: request lengths are a heavy-tail mixture (uniform body,
bounded-Pareto tail; copied from ``ddlbench_tpu.serve.workload``). The SET of
(prompt, output) sizes is fixed by the mix's own ``lengths_seed`` — a pool of
``pool_size`` pairs, offered in the pool's own order to every seed — and
``--seed`` draws the token ids and nothing else. (A seed that permuted the
pool spread the serve rate by 19% on the chip: a window takes a small part
of the pool, and which part decides the rate. PERF.md section 7.)

Training mixes: batches are made on the device inside one jitted function of
(seed, step); every row differs.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Tuple

import numpy as np


def _bounded_pareto(u: float, lo: int, hi: int, alpha: float) -> int:
    x = lo * (1.0 - u * (1.0 - (lo / hi) ** alpha)) ** (-1.0 / alpha)
    return max(lo, min(hi, int(x)))


def heavy_tail_length(rng: random.Random, lo: int, typical: int, hi: int,
                      tail_frac: float = 0.25, alpha: float = 1.2) -> int:
    """Uniform [lo, typical] body; with probability ``tail_frac`` a
    Pareto(alpha) draw anchored at ``typical`` and clipped to ``hi``."""
    if rng.random() < tail_frac and hi > typical:
        return _bounded_pareto(rng.random(), typical, hi, alpha)
    return lo + int(rng.random() * (typical - lo + 1))


def length_pool(mix: Dict) -> List[Tuple[int, int]]:
    """The mix's fixed pool of (prompt_len, max_new) pairs."""
    rng = random.Random(int(mix["lengths_seed"]))
    lo, typ, hi = mix["prompt"]
    olo, otyp, ohi = mix["output"]
    cap = int(mix["max_total"])
    pool = []
    for _ in range(int(mix["pool_size"])):
        s = heavy_tail_length(rng, lo, typ, hi, mix.get("tail_frac", 0.25))
        m = heavy_tail_length(rng, olo, otyp, ohi, mix.get("tail_frac", 0.25))
        s = min(s, cap - olo)
        pool.append((s, min(m, cap - s)))
    return pool


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    max_new: int


class RequestSource:
    """Endless seeded request stream: request ``i`` takes the pool entry at
    ``i mod pool_size`` and token ids drawn from (seed, i)."""

    def __init__(self, mix: Dict, vocab: int, seed: int):
        self.pool = length_pool(mix)
        self.vocab = int(vocab)
        self.seed = int(seed)
        self._next = 0

    def next(self) -> Request:
        i = self._next
        self._next += 1
        s, m = self.pool[i % len(self.pool)]
        toks = np.random.default_rng([self.seed, i]).integers(
            0, self.vocab, size=s, dtype=np.int32)
        return Request(i, toks, m)


class SeededBatches:
    """Training batches from (seed, step), generated on the default device
    by one jitted function; the interface ``data/prefetch.Prefetcher`` reads
    (``batch(epoch, step, train)``, ``steps_per_epoch``)."""

    def __init__(self, seed: int, kind: str, sample_shape: Tuple[int, ...],
                 num_classes: int, batch: int):
        import jax
        import jax.numpy as jnp

        self.batch_size = int(batch)
        base = seed_key(seed)

        def gen(step):
            kx, ky = jax.random.split(jax.random.fold_in(base, step))
            if kind == "tokens":
                (T,) = sample_shape
                seq = jax.random.randint(kx, (batch, T + 1), 0, num_classes,
                                         jnp.int32)
                return seq[:, :-1], seq[:, 1:]
            x = jax.random.uniform(kx, (batch, *sample_shape), jnp.float32)
            x = (x - 0.5) / 0.2887  # unit-variance pixels
            y = jax.random.randint(ky, (batch,), 0, num_classes, jnp.int32)
            return x, y

        self._fn, self._gen = gen, jax.jit(gen)

    def lay_out_like(self, shard_fn) -> None:
        """Make every batch where ``shard_fn`` (a strategy's ``shard_batch``)
        would put it: each chip draws its own rows, ``shard_fn`` then moves
        nothing, and no chip makes, holds and sends out the global batch
        for the others. The values do not change (jax's threefry is
        partitionable: the bits of a position do not depend on the layout)."""
        import jax
        import jax.numpy as jnp

        shapes = jax.eval_shape(self._fn, np.int32(0))
        placed = shard_fn(*(jnp.zeros(s.shape, s.dtype) for s in shapes))
        self._gen = jax.jit(self._fn, out_shardings=tuple(
            a.sharding for a in placed))

    def steps_per_epoch(self, train: bool = True) -> int:
        return 1 << 30

    def batch(self, epoch: int, step: int, train: bool = True):
        return self._gen(np.int32(step))


def seed_key(seed: int):
    """A jax key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
