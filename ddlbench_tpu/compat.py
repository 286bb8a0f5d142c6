"""shard_map / VMA helpers (single home, no copies to drift).

The strategies' shard_map code speaks the VMA (varying-manual-axes) type
system: ``jax.typeof(x).vma`` to read a value's varying axes and
``lax.pcast(..., to="varying")`` to align switch branches / scan carries.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax import lax


def shard_map(f=None, **kw):
    """``jax.shard_map``, also usable as ``@shard_map(mesh=..., ...)``;
    every strategy imports this one symbol."""
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


def vma_of(x) -> Tuple:
    """The value's varying-manual-axes as a tuple; () outside shard_map."""
    return tuple(getattr(jax.typeof(x), "vma", ()) or ())


def pcast_varying(v, axes):
    """Mark ``v`` varying over any of ``axes`` it is not already varying
    over (shard_map branches/carries must agree on VMA types)."""
    missing = tuple(a for a in axes if a not in vma_of(v))
    return lax.pcast(v, missing, to="varying") if missing else v
