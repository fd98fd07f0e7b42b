"""The MoE kernels compile for a TPU v5e at gpt3_medium_moe's widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a v5e chip that is described, not attached, so Mosaic's tiling, DMA and
memory checks run here as they would on the chip (interpret mode checks
none of them).  Shapes are the ones the one-chip and four-chip paths of
``chip_smoke.py`` use, in bf16.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so every test worker must
collect the same tests and only the worker that runs this file loads it.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.moe_fused import kernel as fused_kernel
from repro.kernels.moe_gemm import kernel as gemm_kernel
from repro.kernels.moe_gemm.ops import plan_blocks
from repro.kernels.moe_permute import kernel as permute_kernel

D, F, E, K = 1024, 2048, 64, 2          # gpt3_medium_moe widths, top-2
BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _segments(widths, num_experts):
    offs = np.concatenate([[0], np.cumsum(widths)]).astype(np.int32)
    return plan_blocks(offs, np.arange(len(widths)) % num_experts, 128)


@pytest.mark.parametrize("tokens,width", [
    (4096, 256),      # one-chip train step: 4 x 1024 tokens, cf 2.0
    (8, 8),           # decode step: 8 slots, the gather path's layout
])
def test_local_moe_compiles(one_chip, tokens, width):
    bc, brow, _, _, _ = _segments([width] * E, E)
    S, nb = width * E, brow.shape[0]

    def fn(x, tok, w, row, eid, nv, wi, wo):
        return fused_kernel.local_moe_pallas(
            x, tok, w, row, eid, nv, wi, wi, wo, activation="gelu",
            block_c=bc)

    c = _compile(fn, one_chip, ((tokens, D), BF16), ((S,), I32),
                 ((S,), F32), ((nb,), I32), ((nb,), I32), ((nb,), I32),
                 ((E, D, F), BF16), ((E, F, D), BF16))
    assert c.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("width", [64, 8])
def test_ragged_gemm_compiles(one_chip, width):
    """16 local experts x 4 sources (the four-chip path's delivered
    layout); width 8 gives bf16 row blocks of 8."""
    e_l = E // 4
    bc, brow, _, _, _ = _segments([width] * (e_l * 4), e_l)
    R, nb = width * e_l * 4, brow.shape[0]

    def fn(x, row, eid, nv, wi, wo):
        return gemm_kernel.grouped_ffn_ragged_pallas(
            x, row, eid, nv, wi, wi, wo, activation="gelu", block_c=bc)

    c = _compile(fn, one_chip, ((R, D), BF16), ((nb,), I32), ((nb,), I32),
                 ((nb,), I32), ((e_l, D, F), BF16), ((e_l, F, D), BF16))
    assert c.as_text().count("tpu_custom_call") == 1


def test_permute_compiles(one_chip):
    """1024 tokens per chip into 16 experts x 4 destinations x 128 slots."""
    T, S = 1024, 16 * 4 * 128
    c = _compile(lambda x, idx: permute_kernel.permute_pallas(x, idx),
                 one_chip, ((T + 1, D), BF16), ((S,), I32))
    assert c.as_text().count("tpu_custom_call") == 1


def test_unpermute_compiles(one_chip):
    T, S = 1024, 16 * 4 * 128
    c = _compile(lambda y, idx, w: permute_kernel.unpermute_pallas(y, idx, w),
                 one_chip, ((S + 1, D), BF16), ((T, K), I32), ((T, K), F32))
    assert c.as_text().count("tpu_custom_call") == 1
