"""The one general traffic generator: what ``--seed`` changes and what only
the mix's file does."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest
from benchmarks.harness.traffic import RequestSource, length_pool

MIX = json.load(open(os.path.join(manifest.ROOT, "benchmarks", "traffic",
                                  "serve-closed96.json")))


def sizes(source, n):
    return [(len(r.prompt), r.max_new) for r in (source.next()
                                                 for _ in range(n))]


def test_the_pool_of_sizes_is_the_mix_s_own():
    pool = length_pool(MIX)
    assert len(pool) == MIX["pool_size"] and pool == length_pool(MIX)
    lo, _, hi = MIX["prompt"]
    olo, _, ohi = MIX["output"]
    assert all(lo <= s <= hi and olo <= m <= ohi
               and s + m <= MIX["max_total"] for s, m in pool)
    assert length_pool(dict(MIX, lengths_seed=2)) != pool


@pytest.mark.parametrize("seed", [7, 2**31 + 11, 3000000019])
def test_the_same_seed_gives_the_same_requests(seed):
    a, b = (RequestSource(MIX, 50257, seed) for _ in range(2))
    for _ in range(40):
        ra, rb = a.next(), b.next()
        assert ra.rid == rb.rid and ra.max_new == rb.max_new
        assert np.array_equal(ra.prompt, rb.prompt)
        assert ra.prompt.dtype == np.int32 and ra.prompt.max() < 50257


def test_every_seed_gets_the_same_sizes_in_the_same_order():
    """``--seed`` draws the token ids and nothing else: which part of the
    pool a window takes decides the rate, so no seed may change it."""
    a, b = RequestSource(MIX, 50257, 1), RequestSource(MIX, 50257, 2)
    ra, rb = a.next(), b.next()
    assert len(ra.prompt) == len(rb.prompt)
    assert not np.array_equal(ra.prompt, rb.prompt)  # the tokens do differ
    n = MIX["pool_size"] + 30  # into the second cycle
    assert sizes(a, n) == sizes(b, n)
    assert sizes(RequestSource(MIX, 50257, 3), 96) == length_pool(MIX)[:96]


@pytest.mark.parametrize("kind,shape,classes", [("image", (8, 8, 3), 10),
                                                ("tokens", (16,), 50)])
def test_batches_laid_out_over_the_chips_are_the_same_batches(kind, shape,
                                                              classes):
    """A cell on several chips draws each chip's rows on that chip
    (``lay_out_like``): where a batch lies changes, what it holds does not,
    so the reference, which draws the global batch whole, sees the same
    rows."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.harness.traffic import SeededBatches

    rows = NamedSharding(Mesh(jax.devices()[:4], ("data",)), P("data"))
    whole = SeededBatches(2600000001, kind, shape, classes, 8)
    spread = SeededBatches(2600000001, kind, shape, classes, 8)
    spread.lay_out_like(lambda x, y: (jax.device_put(x, rows),
                                      jax.device_put(y, rows)))
    for step in (0, 3):
        (xa, ya), (xb, yb) = whole.batch(0, step), spread.batch(0, step)
        assert xb.sharding == rows and yb.sharding == rows
        assert len(xa.sharding.device_set) == 1
        assert np.array_equal(np.asarray(xa), np.asarray(xb))
        assert np.array_equal(np.asarray(ya), np.asarray(yb))
