"""Per-layer model summary tables for every model x dataset.

Parity with the reference's run/summary harness + benchmark/network_summary.py
(torchsummary dump of each model on CPU as a shape sanity check,
network_summary.py:27-111). Shape inference here is exact and free: the layer
chain's init computes the boundary shapes without running a forward pass.

Usage:
    python -m ddlbench_tpu.tools.summary                    # full matrix
    python -m ddlbench_tpu.tools.summary -m resnet18 -b mnist
"""

from __future__ import annotations

import argparse
import sys

import jax

from ddlbench_tpu.config import DATASETS
from ddlbench_tpu.models.layers import param_count
from ddlbench_tpu.models.zoo import MODEL_NAMES, get_model
from ddlbench_tpu.models import init_model


def summarize(arch: str, benchmark: str) -> str:
    model = get_model(arch, benchmark)
    # shapes alone: the largest registered model is 30 B parameters
    box = {}

    def init(key):
        params, _, box["shapes"] = init_model(model, key)
        return params

    params_list = jax.eval_shape(init, jax.random.key(0))
    shapes = box["shapes"]
    lines = [
        f"== {arch} / {benchmark} (input {shapes[0]}) ==",
        f"{'layer':<24}{'output shape':<20}{'params':>12}",
        "-" * 56,
    ]
    total = 0
    for layer, p, out_shape in zip(model.layers, params_list, shapes[1:]):
        n = param_count(p)
        total += n
        lines.append(f"{layer.name:<24}{str(out_shape):<20}{n:>12,}")
    lines.append("-" * 56)
    lines.append(f"{'total':<44}{total:>12,}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("-m", "--model", default=None, choices=MODEL_NAMES)
    p.add_argument("-b", "--benchmark", default=None, choices=sorted(DATASETS))
    from ddlbench_tpu.distributed import add_platform_arg, apply_platform

    add_platform_arg(p)
    args = p.parse_args(argv)
    apply_platform(args.platform)
    models = [args.model] if args.model else MODEL_NAMES
    benchmarks = [args.benchmark] if args.benchmark else sorted(DATASETS)
    explicit = bool(args.model and args.benchmark)
    for arch in models:
        for b in benchmarks:
            try:
                out = summarize(arch, b)
            except ValueError:
                # incompatible pair (image arch x token dataset etc.): matrix
                # mode skips it; an explicitly requested pair still errors
                if explicit:
                    raise
                continue
            print(out)
            print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
