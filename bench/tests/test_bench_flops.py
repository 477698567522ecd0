"""Useful FLOPs and kernel bytes against hand counts at the cells' shapes."""

import pytest

from bench import cells, flops


def megatron(layers=2):
    return dict(cells.load_config("megatron-moe-32e-2l")["model"],
                n_layers=layers)


def mixtral():
    return cells.load_config("mixtral-8x7b-2l")["model"]


def test_megatron_prefill_batch_by_hand():
    # per token and layer: q 2048x2048, k and v 2048x512, o 2048x2048
    proj = 2 * (2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048)
    router = 2 * 2048 * 32
    expert = 2 * 3 * 2 * 2048 * 8192          # top-2 SwiGLU experts
    keys = 2048 * 2049 // 2                   # causal pairs per sequence
    scores = 4 * 32 * 64 * keys
    per_seq = 2048 * (proj + router + expert) + scores
    head = 2 * 2048 * 50304                   # last position only
    want = 4 * (2 * per_seq + head)
    assert flops.prefill_flops(megatron(), 4, 2048) == want
    assert want == pytest.approx(3.7826e12, rel=1e-4)


def test_megatron_12_layers_on_four_chips_by_hand():
    one = flops.prefill_flops(megatron(1), 8, 2048) - 8 * 2 * 2048 * 50304
    head = 8 * 2 * 2048 * 50304
    assert flops.prefill_flops(megatron(12), 8, 2048) == 12 * one + head


def test_mixtral_decode_step_by_hand():
    # head_dim 128: q 4096x4096, k and v 4096x1024, o 4096x4096
    proj = 2 * (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096)
    router = 2 * 4096 * 8
    expert = 2 * 3 * 2 * 4096 * 14336
    scores = 4 * 32 * 128 * 385               # position 384 sees 385 keys
    head = 2 * 4096 * 32000
    want = 64 * (2 * (proj + router + expert + scores) + head)
    assert flops.decode_step_flops(mixtral(), 64, 384) == want


def test_mixtral_window_never_binds_below_4096():
    m = mixtral()
    assert m["swa_window"] == 4096
    full = dict(m, swa_window=None)
    assert flops.prefill_flops(m, 64, 128) == flops.prefill_flops(full, 64,
                                                                  128)
    assert flops.decode_step_flops(m, 64, 639) == \
        flops.decode_step_flops(full, 64, 639)
    assert flops.decode_step_flops(m, 1, 5000) < \
        flops.decode_step_flops(full, 1, 5000)


def test_a2a_kernel_bytes_by_hand():
    # pack: 2 pods, 1 stage -> 2 output blocks of 10240 x 2048 bf16
    assert flops.a2a_kernel_bytes(20480, 20480, 2048, 2) == \
        2 * 20480 * 2048 * 2
    # unpack: 2 received blocks into 3 (one is the trash block)
    assert flops.a2a_kernel_bytes(20480, 30720, 2048, 2) == \
        2 * 20480 * 2048 * 2
