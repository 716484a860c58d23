"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel for one chip of a *described*
``v5e:2x2`` topology and compiles it with the TPU compiler, which
refuses what the chip's compiler would refuse (unsupported lowering,
tiling, scoped-VMEM overruns) — the failures interpret-mode tests
cannot see.  The topology is described inside a module fixture (never
at import: only one process may load the TPU library), and the tests
skip when it cannot be described.  Shapes: gemma-2b decode and prefill
GEMMs on the planner's two W4A8 plans (int32 n=2, DSP48E2 n=3),
granite-8b's projections at the tiles ``ops.sdv_blocks`` picks,
UltraNet's 416x416 conv stages, the mamba2-130m short conv, and the
memory-packed ``quant_matmul``.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.datapath import DSP48E2, INT32, plan_bseg, plan_sdv
from repro.kernels import bseg_common, ops
from repro.kernels.bseg_conv1d import bseg_conv1d
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.sdv_matmul import sdv_matmul
from repro.kernels.sdv_matvec import sdv_matvec

SDV_PLANS = {"int32": INT32, "dsp48e2": DSP48E2}
GEMMA_DECODE = [(2048, 2560), (2048, 16384), (16384, 2048)]   # K -> M
GRANITE_8B = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)]


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip):
    """Compile ``fn`` for the described chip at the given shapes; returns
    the compiled executable's HLO text."""
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        with jax.enable_x64(False):
            return jax.jit(fn).lower(*args).compile().as_text()
    return run


def _sdv_plan(datapath):
    return plan_sdv(SDV_PLANS[datapath], 4, 8, signed_a=True,
                    signed_b=True, park_sign_bits=True)


def _word_shape(plan, k, m):
    return bseg_common.sdv_word_spec(plan).plane_shape((k, -(-m // plan.n)))


@pytest.mark.parametrize("datapath", sorted(SDV_PLANS))
@pytest.mark.parametrize("k,m", GEMMA_DECODE)
def test_sdv_matvec_compiles(compile_tpu, datapath, k, m):
    plan = _sdv_plan(datapath)
    hlo = compile_tpu(
        lambda x, w: sdv_matvec(x, w, plan=plan, interpret=False),
        ((k, 8), jnp.int32), (_word_shape(plan, k, m), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("datapath", sorted(SDV_PLANS))
@pytest.mark.parametrize("k,m", [(2048, 16384), (16384, 2048)])
def test_sdv_matmul_compiles(compile_tpu, datapath, k, m):
    """Prefill-chunk rows: 16 slots x an 8-token chunk."""
    plan = _sdv_plan(datapath)
    hlo = compile_tpu(
        lambda x, w: sdv_matmul(x, w, plan=plan, interpret=False),
        ((128, k), jnp.int32), (_word_shape(plan, k, m), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("datapath", sorted(SDV_PLANS))
@pytest.mark.parametrize("k,m", GRANITE_8B)
@pytest.mark.parametrize("rows", [16, 64])              # decode, chunk
def test_sdv_default_tiles_compile(compile_tpu, monkeypatch, datapath, k, m,
                                   rows):
    """``packed_matmul`` with no blocks given: the widened group block
    and its K block fit the chip's scoped VMEM."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    plan = _sdv_plan(datapath)
    hlo = compile_tpu(
        lambda x, w: ops.packed_matmul(x, w, plan=plan, m=m),
        ((rows, k), jnp.int32), (_word_shape(plan, k, m), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("datapath", sorted(SDV_PLANS))
@pytest.mark.parametrize("h,cin,cout,k", [
    (416, 3, 16, 3),             # first stage
    (104, 32, 64, 3),            # body, before the last pools
    (26, 64, 64, 3),             # body at the head's resolution
    (26, 64, 36, 1),             # 1x1 head
])
def test_ultranet_conv_compiles(compile_tpu, monkeypatch, datapath, h, cin,
                                cout, k):
    """The ``packed_conv2d`` dispatch at the 416 frame's layer shapes,
    kernel route lowered for the chip (not the CPU interpreter)."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    plan = plan_bseg(SDV_PLANS[datapath], 4, 4)
    route = ops.select_conv_route((1, h, h, cin), (cout, cin, k, k),
                                  plan=plan)
    assert route in ("bseg_conv2d", "im2col")
    hlo = compile_tpu(
        lambda x, w: ops.packed_conv2d(x, w, plan=plan),
        ((1, h, h, cin), jnp.int32), ((cout, cin, k, k), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("datapath", sorted(SDV_PLANS))
@pytest.mark.parametrize("b,s", [(8, 4), (1, 259)])      # decode, prefill
def test_bseg_conv1d_compiles(compile_tpu, datapath, b, s):
    """mamba2-130m short conv: 4 taps over d_inner = 1536 channels."""
    plan = plan_bseg(SDV_PLANS[datapath], 4, 4)
    ws = bseg_common.word_spec(plan)
    groups = -(-4 // plan.n_k)
    steps = -(-(s + plan.n_k - 1) // plan.n_i)
    need = (steps - 1) * plan.n_i + (groups - 1) * plan.n_k + plan.n_i
    hlo = compile_tpu(
        lambda x, kap: bseg_conv1d(x, kap, plan=plan, s_out=s,
                                   interpret=False),
        ((b, max(need, s + 3), 1536), jnp.int8),
        (ws.plane_shape((groups, 1536)), ws.dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", [8, 128])
def test_quant_matmul_compiles(compile_tpu, rows):
    hlo = compile_tpu(
        lambda x, w, s: quant_matmul(x, w, s, w=4, interpret=False),
        ((rows, 2048), jnp.bfloat16), ((2048, 16384 // 8), jnp.int32),
        ((16384,), jnp.float32))
    assert "tpu_custom_call" in hlo
