"""Pallas token-permutation kernels (TPU row DMAs between HBM buffers).

Both kernels are pure data movement driven by index vectors that are
scalar-prefetched into SMEM.  A TPU vector load or store cannot address a
single row at a dynamic offset of a tiled ``[N, d]`` buffer, and neither
can a DMA.  So the rows travel through a ``[N, 1, d]`` float32 view of the
buffer, whose leading axis is untiled: one row is one DMA, at any index.

``permute``   grid (cdiv(S, BLOCK),): out[s] = x[slot_to_token[s]], a
              chain of HBM -> HBM row DMAs, ``BLOCK`` in flight per step
``unpermute`` grid (cdiv(T, BLOCK),): out[t] = sum_k inv_w[t, k] *
              y[inv_idx[t, k]] — the block's ``K * BLOCK`` picked rows are
              DMA'd into VMEM, reduced with the gate-weight multiply fused
              in, and DMA'd back out

Sentinel convention (shared with ref.py): inputs arrive with one trailing
all-zero row; index == row-count selects it.  ops.py appends that row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

#: Rows moved per grid step (DMAs in flight before the step waits).
BLOCK = 128


def rows_view(x):
    """[N, d] -> the [N, 1, d] float32 view whose rows one DMA can move
    (exact for every dtype the engine moves: bf16, f16, f32)."""
    return x.astype(jnp.float32).reshape(x.shape[0], 1, x.shape[1])


# The analyzer layouts (bottom of file) evaluate this for operands that
# stay in HBM: the whole array is the window, rows move by DMA.

def _hbm_map(*_):
    return (0, 0, 0)


def _dma_rows(n, src_row, dst_row, sem):
    """Start ``n`` row DMAs ``src_row(i) -> dst_row(i)``, then wait for all
    of them (one semaphore; every copy has the same size)."""
    def start(i, _):
        pltpu.make_async_copy(src_row(i), dst_row(i), sem).start()
        return 0

    def wait(i, _):
        pltpu.make_async_copy(src_row(0), dst_row(0), sem).wait()
        return 0

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


def _permute_kernel(idx_ref, x_hbm, o_hbm, sem, *, total: int):
    base = pl.program_id(0) * BLOCK
    n = jnp.minimum(BLOCK, total - base)
    _dma_rows(n, lambda i: x_hbm.at[idx_ref[base + i]],
              lambda i: o_hbm.at[base + i], sem)


def permute_pallas(x_padded, slot_to_token, *, interpret: bool = False):
    """x_padded: [T + 1, d] (last row zeros); slot_to_token: [S] int32 in
    [0, T].  Returns [S, d] rows in sorted capacity-slot order."""
    S = slot_to_token.shape[0]
    d = x_padded.shape[-1]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_permute_kernel, total=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(S, BLOCK),),
            in_specs=[any_spec],
            out_specs=any_spec,
            scratch_shapes=[pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((S, 1, d), jnp.float32),
        interpret=interpret,
    )(slot_to_token, rows_view(x_padded))
    return out.reshape(S, d).astype(x_padded.dtype)


def _unpermute_kernel(idx_ref, w_ref, y_hbm, o_hbm, picked, rows, sem, *,
                      total: int, k: int):
    base = pl.program_id(0) * BLOCK
    n = jnp.minimum(BLOCK, total - base)
    # pick p of token base + i lands in picked[p * BLOCK + i]
    _dma_rows(n * k,
              lambda i: y_hbm.at[idx_ref[(base + i % n) * k + i // n]],
              lambda i: picked.at[(i // n) * BLOCK + i % n], sem)

    def combine(i, _):
        t = base + i
        acc = w_ref[t * k] * picked[i]
        for p in range(1, k):
            acc = acc + w_ref[t * k + p] * picked[p * BLOCK + i]
        rows[i] = acc
        return 0

    jax.lax.fori_loop(0, n, combine, 0)
    _dma_rows(n, lambda i: rows.at[i], lambda i: o_hbm.at[base + i], sem)


def unpermute_pallas(y_padded, inv_idx, inv_w, *, interpret: bool = False):
    """y_padded: [S + 1, d] (last row zeros); inv_idx: [T, K] int32 in
    [0, S]; inv_w: [T, K] float32.  Returns [T, d] float32 combined
    outputs (cast at the caller)."""
    T, K = inv_idx.shape
    d = y_padded.shape[-1]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_unpermute_kernel, total=T, k=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,       # flat inv_idx, inv_w live in SMEM
            grid=(pl.cdiv(T, BLOCK),),
            in_specs=[any_spec],
            out_specs=any_spec,
            scratch_shapes=[pltpu.VMEM((K * BLOCK, 1, d), jnp.float32),
                            pltpu.VMEM((BLOCK, 1, d), jnp.float32),
                            pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((T, 1, d), jnp.float32),
        interpret=interpret,
    )(inv_idx.reshape(-1), inv_w.astype(jnp.float32).reshape(-1),
      rows_view(y_padded))
    return out.reshape(T, d)


# ---------------------------------------------------------------------------
# analyzer layouts (repro.analysis.pallas_check)
# ---------------------------------------------------------------------------


@backend.register_kernel("moe_permute.permute")
def _permute_layouts():
    T, S, d = 96, 256, 128
    idx = np.arange(S, dtype=np.int32) % (T + 1)   # values in [0, T]
    return [backend.KernelLayout(
        kernel="moe_permute.permute",
        grid=(S // BLOCK,),
        prefetch=(idx,),
        blocks=(
            backend.BlockDecl("x_rows", "in", 4, (T + 1, 1, d),
                              (T + 1, 1, d), _hbm_map, hbm=True),
            backend.BlockDecl("o", "out", 4, (S, 1, d), (S, 1, d),
                              _hbm_map, hbm=True),
        ),
    )]


@backend.register_kernel("moe_permute.unpermute")
def _unpermute_layouts():
    T, S, K, d = 256, 128, 2, 128
    idx = (np.arange(T * K, dtype=np.int32) % (S + 1))
    w = np.ones(T * K, np.float32)
    return [backend.KernelLayout(
        kernel="moe_permute.unpermute",
        grid=(T // BLOCK,),
        prefetch=(idx, w),
        blocks=(
            backend.BlockDecl("y_rows", "in", 4, (S + 1, 1, d),
                              (S + 1, 1, d), _hbm_map, hbm=True),
            backend.BlockDecl("o", "out", 4, (T, 1, d), (T, 1, d),
                              _hbm_map, hbm=True),
            backend.BlockDecl("picked", "scratch", 4, (K * BLOCK, 1, d)),
            backend.BlockDecl("rows", "scratch", 4, (BLOCK, 1, d)),
        ),
    )]
