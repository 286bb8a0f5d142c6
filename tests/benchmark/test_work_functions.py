"""FLOPs and bytes from shapes: the model FLOPs behind ``*_step_mfu`` (each
configuration's reference module counts its own) and the kernels' work
behind ``*_roofline``, at the cells' own shapes."""

import pytest

from benchmarks.harness import manifest, peaks
from benchmarks.kernels import (flash_attn, fused_xent, paged_chunk_attn,
                                paged_decode_attn)

MAN = manifest.Manifest()
GPT2, RESNET50 = MAN.config("gpt2-small"), MAN.config("resnet50")
gpt2, resnet = MAN.reference(GPT2), MAN.reference(RESNET50)


def test_resnet50_is_24_6_gflop_per_image_trained():
    """4.09 GMACs forward (the figure every ResNet-50 v1.5 table gives) =
    8.18 GFLOP, x3 for training; convolutions and the classifier only."""
    fwd = resnet.forward_flops(RESNET50, 224)
    assert fwd == pytest.approx(8.178e9, rel=1e-3)
    assert resnet.train_flops_per_sample(RESNET50, (224, 224, 3)) \
        == pytest.approx(24.6e9, rel=5e-3)


def test_resnet_flops_scale_with_resolution_not_classes():
    assert resnet.forward_flops(RESNET50, 448) == pytest.approx(
        resnet.forward_flops(RESNET50, 224) * 4, rel=1e-3)
    assert resnet.forward_flops(dict(RESNET50, num_classes=10), 224) \
        == resnet.forward_flops(RESNET50, 224) - 2 * 2048 * 990


def test_resnet_flops_follow_the_file_s_depth():
    """A ResNet-101 file (3, 4, 23, 3) gets its own count, 15.6 GFLOP
    forward (7.8 GMACs, torchvision's figure), not ResNet-50's."""
    deep = dict(RESNET50, stage_blocks=[3, 4, 23, 3])
    assert resnet.forward_flops(deep, 224) == pytest.approx(15.6e9, rel=0.01)


def test_gpt2_matmul_parameters():
    # 12 blocks x 12 d^2 = 84.9 M, head 768 x 50304 = 38.6 M
    assert gpt2.matmul_params(GPT2, with_head=False) == 12 * 12 * 768**2
    assert gpt2.matmul_params(GPT2) == 12 * 12 * 768**2 + 768 * 50304


def test_gpt2_train_flops_per_sequence():
    T = 1024
    dense = 6 * gpt2.matmul_params(GPT2) * T
    attn = 3 * 12 * 4 * 768 * T * (T + 1) / 2
    got = gpt2.train_flops_per_sample(GPT2, (T,))
    assert got == pytest.approx(dense + attn)
    assert got == pytest.approx(0.817e12, rel=1e-3)
    assert attn / got == pytest.approx(0.071, abs=0.002)


def test_gpt2_token_flops_grow_with_depth():
    shallow = gpt2.served_token_flops(GPT2, 0, True)
    deep = gpt2.served_token_flops(GPT2, 511, True)
    assert shallow == pytest.approx(2 * gpt2.matmul_params(GPT2)
                                    + 12 * 4 * 768)
    assert deep - shallow == 12 * 4 * 768 * 511
    assert gpt2.served_token_flops(GPT2, 0, False) \
        == shallow - 2 * 768 * 50304


def test_flash_attention_work_at_the_cell_shape():
    f, b = flash_attn.work(B=16, H=12, T=1024, dh=64)
    assert f == 6 * 16 * 12 * 1024 * 1024 * 64
    assert b == 12 * 16 * 12 * 1024 * 64 * 2
    p = peaks.DEVICE_PEAKS["TPU v5 lite"]
    # both bounds sit near 0.38 ms a layer: compute-bound by a hair
    assert f / p.flops_bf16 == pytest.approx(3.92e-4, rel=0.01)
    assert b / p.hbm_bytes_per_s == pytest.approx(3.69e-4, rel=0.01)


def test_fused_xent_work_at_the_cell_shape():
    f, b = fused_xent.work(N=16 * 1024, D=768, V=50304)
    assert f == 6 * 16384 * 768 * 50304
    assert b == 2 * (16384 * 768 + 768 * 50304) * 2
    assert f / 1.97e14 == pytest.approx(19.3e-3, rel=0.01)


def test_paged_decode_work_reads_whole_pages_of_payload():
    f, b = paged_decode_attn.work([1, 16, 17], H=12, dh=64, page=16)
    assert f == 4 * 12 * 64 * (1 + 16 + 17)
    assert b == 2 * (16 + 16 + 32) * 12 * 64 * 4
    f8, b8 = paged_decode_attn.work([17], 12, 64, 16, kv_bytes=1)
    assert b8 == 2 * 32 * 12 * 64


def test_paged_chunk_work_counts_visible_keys():
    f, b = paged_chunk_attn.work(start=64, n_real=3, H=12, dh=64, page=16)
    assert f == 4 * 12 * 64 * (65 + 66 + 67)
    assert b == 2 * 80 * 12 * 64 * 4 + 2 * 3 * 12 * 64 * 4


def test_kernel_calls_over_a_window():
    class Ctx:
        config, reference = GPT2, gpt2
        traffic = {"run_config": {"batch_size": 16},
                   "serve_config": {"page": 16, "kv_dtype": "float32"}}
        counters = {"steps": 3, "decode_calls": [[0, 15], [16]],
                    "prefill_calls": [(0, 64), (64, 10)]}

    f1, b1 = flash_attn.work(16, 12, 1024, 64)
    assert flash_attn.calls(Ctx) == (f1 * 36, b1 * 36)
    fx, bx = fused_xent.work(16384, 768, 50304)
    assert fused_xent.calls(Ctx) == (fx * 3, bx * 3)
    fd, bd = paged_decode_attn.calls(Ctx)
    assert fd == 12 * 4 * 12 * 64 * (1 + 16 + 17)
    assert bd == 12 * 2 * (16 + 16 + 32) * 12 * 64 * 4
    fc, _ = paged_chunk_attn.calls(Ctx)
    assert fc == 12 * 4 * 12 * 64 * (sum(range(1, 65)) + sum(range(65, 75)))


def test_call_shapes_come_from_the_configuration_s_reference():
    """The kernel files hold the yardstick (work from shapes) and no
    configuration's key names; gpt2's shapes at the cells' traffic are the
    parent's: flash B 16, H 12, T 1024, dh 64, 12 a step; xent N 16384,
    d 768, V 50304, once a step."""
    train = MAN.traffic("train-b16")
    assert gpt2.kernel_calls("flash_attn", GPT2, train) == [
        (12, dict(B=16, H=12, T=1024, dh=64))]
    assert gpt2.kernel_calls("fused_xent", GPT2, train) == [
        (1, dict(N=16384, D=768, V=50304))]
    serve = MAN.traffic("serve-closed96")
    paged = [(12, dict(H=12, dh=64, page=16, kv_bytes=4))]
    assert gpt2.kernel_calls("paged_decode_attn", GPT2, serve) == paged
    assert gpt2.kernel_calls("paged_chunk_attn", GPT2, serve) == paged
    with pytest.raises(KeyError):
        gpt2.kernel_calls("conv_winograd", GPT2, train)
    # a second configuration at its own shapes, through the same file
    wide = dict(GPT2, n_head=6, n_layer=2, padded_vocab_size=6400)

    class Ctx:
        config, reference, traffic = wide, gpt2, train
        counters = {"steps": 1}

    assert flash_attn.calls(Ctx) == tuple(
        2 * v for v in flash_attn.work(16, 6, 1024, 128))
    assert fused_xent.calls(Ctx) == fused_xent.work(16384, 768, 6400)
    import inspect
    import re
    for mod in (flash_attn, fused_xent, paged_chunk_attn, paged_decode_attn):
        text = inspect.getsource(mod)
        assert not re.search(r"config\[|\bc\[|traffic\[", text), mod.__name__


def test_peaks_table_raises_on_an_unknown_device():
    assert peaks.device_peaks("TPU v5 lite").flops_bf16 == 1.97e14
    assert peaks.device_peaks("TPU v5 lite").hbm_bytes_per_s == 8.19e11
    with pytest.raises(KeyError):
        peaks.device_peaks("cpu")
