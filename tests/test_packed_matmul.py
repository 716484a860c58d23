"""Batched SDV GEMM (kernels/sdv_matmul) + the packed_matmul dispatch
layer: bit-exactness against the pure-jnp oracles over batch shapes,
bitwidth plans (signed and unsigned elements), ragged M/K; and the
dispatch table itself (each (batch, plan, backend) combination selects
the intended kernel)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.datapath import DSP48E2, FP32M, INT32, plan_sdv
from repro.kernels import ops, ref
from repro.kernels.sdv_matmul import sdv_matmul, sdv_num_multiplies

RNG = np.random.default_rng(11)


def _plan(wa, wb, signed_a):
    return plan_sdv(INT32, wa, wb, signed_a=signed_a, signed_b=True,
                    park_sign_bits=signed_a)


def _rand_wx(plan, m, k, batch_shape):
    wa, wb = plan.w_a, plan.w_b
    lo_a, hi_a = (-(1 << wa - 1), 1 << wa - 1) if plan.signed_a \
        else (0, 1 << wa)
    w_mat = RNG.integers(lo_a, hi_a, size=(m, k))
    x = RNG.integers(-(1 << wb - 1), 1 << wb - 1, size=batch_shape + (k,))
    return w_mat, x


@pytest.mark.parametrize("signed_a", [True, False])
@pytest.mark.parametrize("wa", [2, 3, 4, 5])
def test_sdv_matmul_bit_exact(wa, signed_a):
    """Kernel vs oracle over plans w_a in 2..5, signed and unsigned
    elements, M not divisible by the lane count, ragged K blocks."""
    plan = _plan(wa, 8 if wa <= 4 else 4, signed_a)
    m, k = 6 * plan.n + 1, 96            # M % n == 1
    w_mat, x = _rand_wx(plan, m, k, (12,))
    words = ops.prepare_sdv_weights(jnp.asarray(w_mat), plan)
    lanes = sdv_matmul(jnp.asarray(x, jnp.int32), words, plan=plan,
                       br=8, bg=4, bk=32, interpret=True)
    got = np.asarray(lanes).reshape(12, -1)[:, :m]
    assert (got == x @ w_mat.T).all(), (plan, got[0, :4])


@pytest.mark.parametrize("datapath", [INT32, DSP48E2], ids=lambda d: d.name)
@pytest.mark.parametrize("rows,g", [(16, 1100), (64, 490)])
def test_sdv_matmul_bit_exact_default_tiles(datapath, rows, g):
    """The kernel at the tiles ``sdv_blocks`` picks for a thin row
    block: a group block wider than 128 lanes over a ragged G, two K
    blocks, on the one-limb int32 n=2 and two-limb DSP48E2 n=3 words."""
    plan = plan_sdv(datapath, 4, 8, signed_a=True, signed_b=True,
                    park_sign_bits=True)
    m, k = g * plan.n - 1, 16
    br, bg, _ = ops.sdv_blocks(rows, k, g, plan)
    assert bg > 128 and g % bg, (rows, g, bg)
    w_mat, x = _rand_wx(plan, m, k, (rows,))
    words = ops.prepare_sdv_weights(jnp.asarray(w_mat), plan)
    assert words.shape[-1] == g
    y = ops.packed_matmul(jnp.asarray(x, jnp.int32), words, plan=plan,
                          m=m, block_k=k // 2)
    assert (np.asarray(y) == x @ w_mat.T).all(), (plan, br, bg)


@pytest.mark.parametrize("batch_shape", [(1,), (3,), (20,), (2, 5)])
def test_packed_matmul_batch_shapes(batch_shape):
    """Dispatch entry point is exact for every batch rank/size,
    including K not divisible by the K block."""
    plan = _plan(4, 8, True)
    m, k = 37, 100                        # K % block_k != 0
    w_mat, x = _rand_wx(plan, m, k, batch_shape)
    words = ops.prepare_sdv_weights(jnp.asarray(w_mat), plan)
    want = x @ w_mat.T
    for mode in ("auto", "sdv_matmul", "sdv_matvec", "ref"):
        y = ops.packed_matmul(jnp.asarray(x), words, plan=plan, m=m,
                              mode=mode, block_rows=8, block_g=8,
                              block_k=32)
        assert y.shape == batch_shape + (m,)
        assert (np.asarray(y) == want).all(), (mode, batch_shape)


def test_packed_matmul_unsigned_elements():
    plan = _plan(3, 4, False)
    m, k = 4 * plan.n + 2, 64
    w_mat, x = _rand_wx(plan, m, k, (9,))
    words = ops.prepare_sdv_weights(jnp.asarray(w_mat), plan)
    want = x @ w_mat.T
    for mode in ("auto", "sdv_matmul", "ref"):
        y = ops.packed_matmul(jnp.asarray(x), words, plan=plan, m=m,
                              mode=mode, block_rows=4, block_g=4,
                              block_k=16)
        assert (np.asarray(y) == want).all(), mode


def test_ref_word_decode_roundtrip():
    for signed_a in (True, False):
        plan = _plan(4, 8, signed_a)
        m, k = 3 * plan.n, 16
        w_mat, _ = _rand_wx(plan, m, k, (1,))
        words = ops.prepare_sdv_weights(jnp.asarray(w_mat), plan)
        back = np.asarray(ref.sdv_unpack_words_ref(words, plan=plan))
        assert (back.T[:m] == w_mat).all()


# ---------------------------------------------------------------------------
# the SDV tile rule (ops.sdv_blocks)
# ---------------------------------------------------------------------------

GRANITE_8B = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)]


@pytest.mark.parametrize("datapath", [INT32, DSP48E2], ids=lambda d: d.name)
@pytest.mark.parametrize("k,m", GRANITE_8B + [(64, 36)])
@pytest.mark.parametrize("rows", [1, 3, 8, 16, 64, 128, 169])
def test_sdv_blocks(datapath, k, m, rows):
    """Every granite-8b projection (and UltraNet's 1x1 head) at decode,
    chunk and GEMM row counts: within the vreg target and the VMEM
    budget, K a multiple of ``bk``, ragged padding under 15%, and the
    fixed ``[128, 128]`` tile from 128 rows up."""
    plan = plan_sdv(datapath, 4, 8, signed_a=True, signed_b=True,
                    park_sign_bits=True)
    g = -(-m // plan.n)
    br, bg, bk = ops.sdv_blocks(rows, k, g, plan)
    limbs = 2 if datapath is DSP48E2 else 1
    assert br == min(rows, 128)
    assert bg == g or bg % 128 == 0
    assert -(-br // 8) * -(-bg // 128) <= ops.SDV_TILE_VREGS
    assert -(-g // bg) * bg - g < 0.15 * g
    assert k % bk == 0
    assert limbs * bk * bg * 4 * 2 <= ops.SDV_WEIGHT_VMEM_BYTES
    if rows >= 128:
        assert (br, bg) == (128, min(128, g))
    elif rows <= 16 and g > 128:
        assert bg > 128                 # a thin row block widens


def test_sdv_blocks_explicit_pass_through():
    plan = _plan(4, 8, True)
    assert ops.sdv_blocks(16, 4096, 2048, plan, block_rows=8, block_g=256,
                          block_k=128) == (8, 256, 128)
    # rows cap the row block; a K block that does not divide K is all of K
    assert ops.sdv_blocks(5, 100, 40, plan, block_rows=8, block_g=8,
                          block_k=32) == (5, 8, 100)
    # a given group block still sizes the default K block
    assert ops.sdv_blocks(16, 4096, 2048, plan, block_g=128)[1:] == (128, 512)


# ---------------------------------------------------------------------------
# the dispatch table (see kernels/ops.py module docstring)
# ---------------------------------------------------------------------------

def test_dispatch_table_auto():
    signed = _plan(4, 8, True)
    unsigned = _plan(4, 8, False)
    fp32m = plan_sdv(FP32M, 4, 8, signed_a=True, signed_b=True)
    sel = ops.select_packed_route
    # (batch rows, plan, backend/use_kernel) -> intended kernel
    assert sel(1, plan=signed) == "sdv_matvec"
    assert sel(ops.GEMV_MAX_ROWS, plan=signed) == "sdv_matvec"
    assert sel(ops.GEMV_MAX_ROWS + 1, plan=signed) == "sdv_matmul"
    assert sel(256, plan=signed) == "sdv_matmul"
    # the GEMV kernel only stores signed elements
    assert sel(1, plan=unsigned) == "sdv_matmul"
    # fp32m rounds past the mantissa: spill tracking invalid -> ref
    assert sel(256, plan=fp32m) == "ref"
    # no pallas backend -> pure-jnp path
    assert sel(256, plan=signed, use_kernel=False) == "ref"
    # no SDV plan: memory-packed lane words
    assert sel(256) == "quant_matmul"
    assert sel(256, use_kernel=False) == "ref"


def test_dispatch_table_explicit_modes():
    signed = _plan(4, 8, True)
    unsigned = _plan(4, 8, False)
    fp32m = plan_sdv(FP32M, 4, 8, signed_a=True, signed_b=True)
    sel = ops.select_packed_route
    assert sel(999, plan=signed, mode="sdv_matvec") == "sdv_matvec"
    assert sel(1, plan=signed, mode="sdv_matmul") == "sdv_matmul"
    assert sel(1, plan=signed, mode="ref") == "ref"
    with pytest.raises(ValueError):
        sel(1, mode="sdv_matmul")                  # needs a plan
    with pytest.raises(ValueError):
        sel(1, plan=fp32m, mode="sdv_matmul")      # not exact-wrap
    with pytest.raises(ValueError):
        sel(1, plan=unsigned, mode="sdv_matvec")   # GEMV is signed-only
    with pytest.raises(ValueError):
        sel(1, plan=signed, mode="quant_matmul")   # wrong weight format
    with pytest.raises(ValueError):
        sel(1, mode="bogus")


def test_packed_matmul_rejects_float_on_sdv_routes():
    """Float activations must be rejected, not silently truncated, by
    the integer datapath routes (quantize first — sdv_matmul_apply)."""
    plan = _plan(4, 8, True)
    words = ops.prepare_sdv_weights(jnp.ones((plan.n, 16), jnp.int32), plan)
    xf = jnp.ones((4, 16), jnp.float32) * 0.5
    for mode in ("auto", "sdv_matmul", "sdv_matvec", "ref"):
        with pytest.raises(ValueError):
            ops.packed_matmul(xf, words, plan=plan, mode=mode)


def test_packed_matmul_quant_route():
    """The memory-packed side of the table (float activations)."""
    x = RNG.standard_normal((2, 3, 64)).astype(np.float32)
    wint = RNG.integers(-8, 8, (64, 32))
    wp = ref.pack_words_ref(jnp.asarray(wint), w=4)
    sc = (RNG.standard_normal(32) * 0.1).astype(np.float32)
    want = np.asarray(ref.quant_matmul_ref(
        jnp.asarray(x.reshape(-1, 64)), jnp.asarray(wint),
        jnp.asarray(sc))).reshape(2, 3, 32)
    for use_kernel in (True, False):
        y = ops.packed_matmul(jnp.asarray(x), wp, scale=jnp.asarray(sc),
                              w_bits=4, use_kernel=use_kernel,
                              block_rows=8, block_g=16, block_k=32)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5,
                                   atol=1e-4)


def test_sdv_num_multiplies():
    plan = _plan(4, 8, True)   # n = 2
    assert sdv_num_multiplies(64, 256, 512, plan) \
        == 64 * (256 // plan.n) * 512
    # reduction vs the naive count is exactly the packing density
    assert 64 * 256 * 512 / sdv_num_multiplies(64, 256, 512, plan) == plan.n


# ---------------------------------------------------------------------------
# model wiring: SDVLinear end to end
# ---------------------------------------------------------------------------

def test_sdv_linear_apply_matches_materialized():
    from repro.models.quantized import (default_sdv_plan, materialize,
                                        pack_linear_sdv, sdv_matmul_apply)
    plan = default_sdv_plan(4, 8)
    kernel = jnp.asarray(RNG.standard_normal((48, 33)).astype(np.float32))
    qw = pack_linear_sdv(kernel, plan)
    x = jnp.asarray(RNG.standard_normal((5, 48)).astype(np.float32))
    y = np.asarray(sdv_matmul_apply(
        dataclasses.replace(qw, use_kernel=True), x))
    # same quantized weights, dense float path; the only difference is
    # the 8-bit dynamic activation quantization
    want = np.asarray(x @ materialize(qw, jnp.float32))
    err = np.abs(y - want).max() / max(np.abs(want).max(), 1e-6)
    assert err < 0.02, err


def test_serve_params_sdv_mode():
    from repro.models.quantized import SDVLinear, is_packed, serve_params
    params = {
        "layer": {"kernel": jnp.ones((64, 32), jnp.float32)},
        "moe": {"wi_gate": jnp.ones((4, 16, 32), jnp.float32)},
        "lm_head": jnp.ones((64, 128), jnp.float32),
    }
    # 2-D kernels -> SDVLinear, >2-D expert banks stay memory-packed
    qp = serve_params(params, bits=4, min_size=1, compute="sdv")
    assert isinstance(qp["layer"]["kernel"], SDVLinear)
    assert isinstance(qp["lm_head"], SDVLinear)
    assert is_packed(qp["moe"]["wi_gate"])
    assert not isinstance(qp["moe"]["wi_gate"], SDVLinear)
    with pytest.raises(ValueError):
        serve_params(params, compute="bogus")
