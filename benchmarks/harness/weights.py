"""Weights from ``--seed``, made on the device in ONE jitted call, as a flat
dict ``{"layer/key/...": array}``. The benchmark owns these arrays: the
program gets them unflattened into its own tree, the plain reference reads
the flat dict, and neither sees anything the other made.

Rules by leaf (the configuration file's ``weights`` group chooses the matrix
rule): every leaf is random, biases and norm scales too, so that no leaf's
gradient is trivially zero or symmetric.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

from benchmarks.harness.traffic import seed_key


def flat_specs(tree, layer_names) -> Dict[str, Tuple[int, ...]]:
    """{"layer/key/sub": shape} of a program's per-layer params list (arrays
    or ShapeDtypeStructs)."""
    return {k: tuple(leaf.shape)
            for k, leaf in flat_leaves(tree, layer_names).items()}


def flat_leaves(tree, layer_names) -> Dict:
    """{"layer/key/sub": leaf} of a per-layer list."""
    import jax

    out = {}
    for name, p in zip(layer_names, tree):
        for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
            out["/".join([name] + [str(getattr(k, "key", k))
                                   for k in path])] = leaf
    return out


def unflatten(flat: Dict, tree, layer_names):
    """``flat`` laid out as ``tree`` (the program's per-layer list)."""
    import jax

    out = []
    for name, p in zip(layer_names, tree):
        paths, treedef = jax.tree_util.tree_flatten_with_path(p)
        leaves = [flat["/".join([name] + [str(getattr(k, "key", k))
                                          for k in path])]
                  for path, _ in paths]
        out.append(jax.tree_util.tree_unflatten(treedef, leaves))
    return out


def make_weights(seed: int, specs: Dict[str, Tuple[int, ...]], rules: Dict):
    """All leaves in one jitted call, float32, on the default device."""
    import jax
    import jax.numpy as jnp

    names = sorted(specs)
    matrix = rules["matrix"]

    def gen(key):
        out = {}
        for i, name in enumerate(names):
            shape = specs[name]
            k = jax.random.fold_in(key, i)
            n = jax.random.normal(k, shape, jnp.float32)
            leaf = name.rsplit("/", 1)[-1]
            if len(shape) >= 2:
                if matrix == "he_fan_out" and len(shape) == 4:
                    std = math.sqrt(2.0 / (shape[0] * shape[1] * shape[3]))
                elif matrix == "he_fan_out":  # a dense layer: 1/sqrt(fan_in)
                    std = 1.0 / math.sqrt(shape[0])
                else:
                    std = float(matrix)
                out[name] = n * std
            elif leaf == "scale":
                out[name] = 1.0 + rules.get("scale_jitter", 0.1) * n
            else:
                out[name] = rules.get("bias_std", 0.02) * n
        return out

    return jax.jit(gen)(seed_key(seed))
