"""``work.py`` against hand counts: operations and bytes are the
layer's (from its shape and declared bit widths), not the kernel's."""
import json
import os

import pytest

import work

BENCH = os.path.dirname(os.path.abspath(__file__))
PEAK = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]


def cfg(name):
    return json.load(open(os.path.join(BENCH, "configs", f"{name}.json")))


def test_granite_projection_by_hand():
    # wq of one granite layer over a 16-row decode batch, W4A8
    w = work.linear_call(16, 4096, 4096, w_bits=4, a_bits=8, out_bytes=4)
    assert w.ops == 2 * 16 * 4096 * 4096 == 536_870_912
    # int4 weights + int8 activations + int32 outputs
    assert w.bytes == 8_388_608 + 65_536 + 262_144
    t, bound = work.least_time(w, PEAK, integer=True)
    assert bound == "memory" and t == pytest.approx(8_716_288 / 819e9)


def test_granite_layer_and_step():
    c = cfg("granite-8b-d9-sdv")
    macs = sum(k * n for _, k, n in work.decoder_linears(c))
    # q, k, v, o, gate, up, down of one granite layer
    assert macs == (4096 * 4096 * 2 + 4096 * 1024 * 2
                    + 3 * 4096 * 14336) == 218_103_808
    step = work.step_linear_work(c, 16, head=False)
    assert step.ops == 9 * 2 * 16 * 218_103_808
    # the tied head is a bf16 matmul outside the packed kernels; an
    # untied one is packed like the layers
    assert work.step_linear_work(c, 16, head=True) == step
    untied = dict(c, tie_word_embeddings=False)
    with_head = work.step_linear_work(untied, 16, head=True)
    assert with_head.ops - step.ops == 2 * 16 * 4096 * 49152
    # the memory route's activations and outputs are bf16
    m = work.step_linear_work(cfg("granite-8b-d9-mem"), 16, head=False)
    assert m.ops == step.ops
    assert m.bytes - step.bytes == 9 * sum(
        16 * k * (16 - 8) / 8 + 16 * n * (2 - 4)
        for _, k, n in work.decoder_linears(c))


def test_model_ops_per_token():
    c = cfg("granite-8b-d9-sdv")
    # 2 x (9 layers' projections + the LM head) at an empty context
    assert work.model_ops_per_token(c, 0) == 2 * (
        9 * 218_103_808 + 4096 * 49152) == 4_328_521_728
    # attention adds 2 x (scores + values) x heads x head size per
    # cached position and layer
    assert work.model_ops_per_token(c, 100) \
        - work.model_ops_per_token(c, 0) == 2 * 9 * 2 * 32 * 128 * 100


def test_ultranet_stage_by_hand():
    c = cfg("ultranet-416")
    first = work.ultranet_convs(c)[0]
    assert first == {"kind": "stage", "h": 416, "w": 416, "cin": 3,
                     "cout": 16, "k": 3}
    w = work.conv_call(416, 416, 3, 16, 3, w_bits=4, a_bits=4)
    assert w.ops == 2 * 416 * 416 * 16 * 3 * 9 == 149_520_384
    assert w.bytes == 16 * 3 * 9 / 2 + 416 * 416 * 3 / 2 + 416 * 416 * 16 * 4
    head = work.ultranet_convs(c)[-1]
    assert (head["h"], head["cin"], head["cout"], head["k"]) == (26, 64, 36, 1)
    total = sum(2 * s["h"] * s["w"] * s["cin"] * s["cout"] * s["k"] ** 2
                for s in work.ultranet_convs(c))
    assert work.ultranet_frame_ops(c) == total
    assert work.ultranet_stage_work(c).ops == total - 2 * 26 * 26 * 64 * 36


def test_count_is_the_layers_not_the_kernels():
    """The SDV kernels keep DSP48E2 words of three int4 lanes in two
    int32 limbs, and spend limb and spill-tracker ops per multiply.
    Counting either would move the roofline with the kernel: the bytes
    of the words are 5.3x the declared int4 weights, and one wide
    multiply carries three MACs."""
    k, n = 4096, 14336
    declared = work.linear_call(16, k, n, w_bits=4, a_bits=8, out_bytes=4)
    word_bytes = k * -(-n // 3) * 2 * 4          # [2, K, G] int32 limbs
    assert word_bytes == pytest.approx(5.33 * k * n / 2, rel=1e-3)
    assert declared.bytes < word_bytes
    wide_multiplies = 16 * k * -(-n // 3)
    assert declared.ops == 2 * 16 * k * n != 2 * wide_multiplies
