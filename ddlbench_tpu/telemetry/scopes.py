"""The program's names for its own device work.

Every device operation the train step compiles to carries, in its HLO
``metadata={op_name="..."}``, the path of ``jax.named_scope``s it was
traced under, e.g.::

    jit(train_step)/transpose(jvp(group1_block3))/bn/reduce_sum
    jit(train_step)/jvp(block7)/attn/flash_attn_fwd/pallas_call
    jit(train_step)/optimizer/mul

A path is ``<layer instance>/<kind>`` for model work (the instance is
``Layer.name``, opened once in ``models/layers.apply_slice``; the kind is
one of the tokens below, opened where that kind of work is written) or a
step phase (``optimizer``, ``grad_sync``). Forward and backward need no
scope: jax wraps the outermost scope of differentiated code in ``jvp(..)``
and its transpose in ``transpose(jvp(..))``, and XLA keeps the path on
every instruction, a fusion taking its root's.

A named scope is trace-time metadata: it adds no operation and changes no
instruction of the compiled program, so scopes are always on. The readers
that turn these names into device time per scope live with the benchmark
(``benchmarks/harness/scopes.py``); ``PERF.md`` section 3 lists which
metric reads which token.
"""

from __future__ import annotations

import jax

# kinds of model work, inside a layer instance's scope
CONV = "conv"    # layers.conv2d
BN = "bn"        # layers.batchnorm
POOL = "pool"    # max_pool / avg_pool / global_avg_pool
FC = "fc"        # layers.dense
EMBED = "embed"  # transformer.embed
LN = "ln"        # transformer.layer_norm
ATTN = "attn"    # qkv projection, attention core (flash kernels), out proj
MLP = "mlp"      # the transformer block's MLP (its LayerNorm is under ln)
HEAD = "head"    # lm_head's projection to the vocabulary
LOSS = "loss"    # cross entropy, fused (<head instance>/loss) or not
KINDS = (CONV, BN, POOL, FC, EMBED, LN, ATTN, MLP, HEAD, LOSS)

# parts of a layer's work that a model names beside the kinds. The kinds are
# a closed vocabulary on the benchmark's side (an accepted test of
# tests/benchmark/ pins the ten), so these are read by a reader of their own
# (benchmarks/metrics/readers/scope_part_ms.py): the innermost token of
# KINDS + PARTS on an instruction's path decides what it counts as.
LATENT = "latent"    # kanana2: kv_a, the latent's norm, kv_b, k and v assembled
ROUTE = "route"      # router, top-k, sort, gather, weighted scatter
EXPERTS = "experts"  # the grouped products over the experts held (dropless)
PARTS = (LATENT, ROUTE, EXPERTS)
# three parts INSIDE a kind or a part above. They are not in PARTS (an accepted
# test of tests/benchmark/ holds PARTS to the three that the accepted part
# metrics list): a metric whose file lists them reads the innermost, one
# whose file lists the three counts them as the scope around them (cca_mix
# as attn, router as route).
# zaya, inside attn: value shift, q-k mean, both causal convolutions, the L2
# normalisation (the projections, RoPE, the flash kernels, W_o stay attn)
CCA_MIX = "cca_mix"
# the family's router, inside route — zaya: W_d, the carried state, the MLP,
# softmax, argmax; smallthinker: the logits' matmul, top-6 and the softmax over
# the chosen, where they run (before attention) — sort, gather and weighted
# scatter stay route
ROUTER = "router"
# smallthinker, inside attn: a WINDOW layer's attention core, RoPE and the
# flash kernels under their window (the projections and W_o stay attn; a
# global layer's core is attn alone)
WINDOW = "window"

# step phases outside the differentiated model
OPTIMIZER = "optimizer"  # common.make_optimizer's update
GRAD_SYNC = "grad_sync"  # dp's explicit gradient collectives, /bucket<b>
PHASES = (OPTIMIZER, GRAD_SYNC)

VOCABULARY = KINDS + PHASES

scope = jax.named_scope
