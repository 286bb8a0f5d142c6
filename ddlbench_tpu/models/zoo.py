"""Model registry: (arch, dataset) -> LayerModel.

Replaces the reference's three parallel model families and per-dataset
directories (SURVEY.md §2 B5-B7) with one registry; the dataset spec chooses
the stem/classifier variant.
"""

from __future__ import annotations

from ddlbench_tpu.config import DATASETS, DatasetSpec
from ddlbench_tpu.models.layers import LayerModel
from ddlbench_tpu.models.mobilenetv2 import build_mobilenetv2
from ddlbench_tpu.models.resnet import build_resnet
from ddlbench_tpu.models.vgg import build_vgg

MODEL_NAMES = ("resnet18", "resnet50", "resnet152", "vgg11", "vgg16",
               "mobilenetv2", "lenet", "alexnet", "squeezenet", "resnext50",
               "densenet121", "inception", "nasnet", "transformer_t",
               "transformer_s",
               "transformer_m", "transformer_moe_s", "seq2seq_s", "seq2seq_m",
               "seq2seq_lstm_s", "kanana2_30b_a3b", "zaya1_8b",
               "smallthinker_21b_a3b")

ARCH_HELP = ("one of MODEL_NAMES; kanana2_30b_a3b, zaya1_8b and "
             "smallthinker_21b_a3b may carry the share one chip holds: "
             "-l<layers kept>, -e<experts held>[r<rank>], e.g. "
             "kanana2_30b_a3b-l5-e8, zaya1_8b-l5-e8r1, "
             "smallthinker_21b_a3b-l4-e16 (models/kanana2.py, models/zaya.py, "
             "models/smallthinker.py: each routes its own way, "
             "models/dropless.py computes the held experts' part for all)")


def _share_family(arch: str):
    """The module of the family whose arch strings carry a chip's share
    (``is_family``, ``parse_arch``, ``build``), None for any other arch."""
    from ddlbench_tpu.models import kanana2, smallthinker, zaya

    return next((m for m in (kanana2, zaya, smallthinker)
                 if m.is_family(arch)), None)


def arch_name(arch: str) -> str:
    """``arch`` if this registry can build it (the argparse ``type`` of
    every ``--model``/``--arch`` option), else a ValueError naming what it
    can."""
    family = _share_family(arch)
    if family is not None:
        family.parse_arch(arch)  # a share the family cannot cut raises
    if arch in MODEL_NAMES or family is not None:
        return arch
    raise ValueError(f"unknown arch {arch!r}; known: {MODEL_NAMES}")


def collects_aux_loss(arch: str) -> bool:
    """True for the Switch-routed archs (models/moe.py): their router's
    capacity and load-balance loss are statistics of the whole routed batch,
    collected through a trace-time sink — what a checkpointed layer cannot
    let out and a shard_map over the batch would make per-shard. (The
    dropless routers of models/kanana2.py, models/zaya.py and
    models/smallthinker.py route token by token and collect nothing.)"""
    from ddlbench_tpu.models.moe import _VARIANTS

    return arch in _VARIANTS


def get_model(arch: str, dataset: str | DatasetSpec,
              moe_capacity_factor: float = 1.25,
              attention_backend: str = "auto") -> LayerModel:
    """``attention_backend`` (config.ATTENTION_BACKENDS) goes to the builders
    of the archs that attend, as ``moe_capacity_factor`` goes to the
    Switch-routed ones: a model is built for one backend and carries it in
    its layers' closures."""
    spec = dataset if isinstance(dataset, DatasetSpec) else DATASETS[dataset]
    if arch.startswith("seq2seq"):
        if spec.kind != "seq2seq":
            raise ValueError(f"{arch} requires a seq2seq dataset, got {spec.name}")
        if "lstm" in arch:
            # recurrent (GNMT-class) variant, scan-based (models/lstm.py)
            from ddlbench_tpu.models.lstm import build_lstm_seq2seq

            return build_lstm_seq2seq(arch, spec.image_size,
                                      spec.num_classes, spec.src_len)
        from ddlbench_tpu.models.seq2seq import build_seq2seq

        return build_seq2seq(arch, spec.image_size, spec.num_classes,
                             spec.src_len, attention_backend)
    family = _share_family(arch)
    if family is not None:
        if spec.kind != "tokens":
            raise ValueError(f"{arch} requires a token dataset, got {spec.name}")
        return family.build(arch, spec.image_size, spec.num_classes,
                            attention_backend)
    if arch.startswith("transformer"):
        if spec.kind != "tokens":
            raise ValueError(f"{arch} requires a token dataset, got {spec.name}")
        if "moe" in arch:
            from ddlbench_tpu.models.moe import build_transformer_moe

            return build_transformer_moe(
                arch, spec.image_size, spec.num_classes,
                capacity_factor=moe_capacity_factor,
                attention_backend=attention_backend,
            )
        from ddlbench_tpu.models.transformer import build_transformer

        return build_transformer(arch, spec.image_size, spec.num_classes,
                                 attention_backend)
    if spec.kind != "image":
        raise ValueError(f"{arch} requires an image dataset, got {spec.name}")
    if arch.startswith(("inception", "nasnet")):
        # branchy DAG archs: strategies run the articulation-block chain
        # form; the auto-partition path profiles the real DAG
        # (models/branchy.py). nasnet's two-input cells make its DAG
        # non-series-parallel, unlike inception's SP modules.
        from ddlbench_tpu.models.branchy import get_dag, to_chain

        dag = get_dag(arch, spec.image_size, spec.num_classes)
        if dag is None:
            raise ValueError(f"unknown branchy arch {arch!r}")
        return to_chain(dag)
    if arch.startswith("resnet"):
        return build_resnet(arch, spec.image_size, spec.num_classes)
    if arch.startswith("vgg"):
        return build_vgg(arch, spec.image_size, spec.num_classes)
    if arch == "mobilenetv2":
        return build_mobilenetv2(arch, spec.image_size, spec.num_classes)
    from ddlbench_tpu.models.extra import BUILDERS as _EXTRA

    if arch in _EXTRA:
        return _EXTRA[arch](spec.image_size, spec.num_classes)
    raise ValueError(f"unknown arch {arch!r}; known: {MODEL_NAMES}")
