"""Fused dispatch→GEMM→combine Pallas megakernel (permute-free local path).

The kernel-on engine previously made three HBM round trips over the same
rows per layer: ``permute`` row-DMAs tokens into a sorted [S, d] capacity
buffer, the ragged grouped GEMM reads it back, and ``unpermute`` scatters
expert outputs into token order.  For *local* traffic (the stage-0 self
level, and every stage of a unit mesh) nothing ever leaves the device, so
the sorted buffer is pure staging — this kernel deletes it.

Grid: ``(row-block, f-block)`` — the same static block decomposition the
ragged GEMM uses (``moe_gemm.ops.plan_blocks``) — with **five**
scalar-prefetch SMEM vectors: the permute's ``slot_to_token`` map and
per-slot combine weights feed the GEMM's ``block_row`` / ``block_eid`` /
``block_nvalid`` vectors directly:

    slot_to_token[s]  source token of capacity slot ``s`` (sentinel = T)
    slot_w[s]         combine weight of slot ``s`` (0 for empty slots)
    block_row[b]      row-block index of block ``b`` in slot space
    block_eid[b]      expert whose weights block ``b`` multiplies
    block_nvalid[b]   runtime valid-row count of block ``b`` (0..bc)

Each grid step's *gather prologue* (first f block of a row block) DMAs
its live input rows straight from the token buffer in HBM via
``slot_to_token`` — the sorted [S, d] buffer never exists in HBM.
``pl.when(block_nvalid > 0)`` gates the whole body exactly as in the
ragged GEMM, so slack blocks still issue zero DMAs and zero matmuls.  The
*combine epilogue* (last f block) mirrors ``unpermute``: each live slot's
f32 down-projection row, times its gate weight, is added into its token's
row of the [T, d] output by a read-modify-write of that row in HBM
(valid slots are a segment prefix, so none is the sentinel, and a token
picks an expert at most once, so the rows of one block are distinct and
their read-modify-writes cannot race).

Neither the tokens nor the output is resident in VMEM, so the working set
is the weight blocks plus three ``[block_c, d]`` f32 buffers whatever the
token count: the kernel takes a training step's 8k tokens as it takes a
decode step's few.  Rows travel through the ``[N, 1, d]`` float32 view of
``moe_permute.kernel.rows_view`` (one row, one DMA), and the output starts
as an aliased zero buffer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend
from repro.kernels.moe_gemm.kernel import _ffn_body
from repro.kernels.moe_permute.kernel import _dma_rows, _hbm_map, rows_view


# BlockSpec index maps, named so the analyzer layout (bottom of file)
# evaluates the exact functions the pallas_call uses.

def _fused_win_map(b, j, tok, w, row, eid, nv):
    return (eid[b], 0, j)


def _fused_wout_map(b, j, tok, w, row, eid, nv):
    return (eid[b], j, 0)


def _fused_kernel(tok_ref, w_ref, row_ref, eid_ref, nvalid_ref,
                  x_hbm, win_ref, wgate_ref, wout_ref, _zeros, o_hbm,
                  acc_ref, xblk_ref, rows_ref, sem, *, activation: str,
                  block_c: int):
    b = pl.program_id(0)               # row-block index (scalar-prefetched)
    j = pl.program_id(1)               # f-block index (sequential)
    nf = pl.num_programs(1)
    nv = nvalid_ref[b]                 # runtime valid rows of this block
    base = row_ref[b] * block_c        # first slot of this block

    # the same occupancy predicate as the ragged GEMM: row blocks past a
    # segment's realized count do zero gathers, zero MXU work, zero stores
    @pl.when(nv > 0)
    def _compute():
        @pl.when(j == 0)
        def _gather():
            # dispatch fused in: DMA the block's live rows straight from
            # the token buffer; rows past nv stay stale and are masked
            _dma_rows(nv, lambda i: x_hbm.at[tok_ref[base + i]],
                      lambda i: rows_ref.at[i], sem)

            def body(i, _):
                xblk_ref[pl.ds(i, 1), :] = rows_ref[i]
                return 0
            jax.lax.fori_loop(0, nv, body, 0)

        x = xblk_ref[...].astype(win_ref.dtype)
        part = _ffn_body(x, win_ref, wgate_ref, wout_ref,
                         activation=activation)
        rows = jax.lax.broadcasted_iota(jnp.int32, part.shape, 0)
        part = jnp.where(rows < nv, part, 0.0)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _acc():
            acc_ref[...] += part

        @pl.when(j == nf - 1)
        def _scatter():
            # combine fused in: read-modify-write each live slot's token
            # row with the gate-weight multiply applied
            def out_row(i):
                return o_hbm.at[tok_ref[base + i]]

            _dma_rows(nv, out_row, lambda i: rows_ref.at[i], sem)

            def body(i, _):
                rows_ref[i] = (rows_ref[i]
                               + w_ref[base + i] * acc_ref[pl.ds(i, 1), :])
                return 0
            jax.lax.fori_loop(0, nv, body, 0)
            _dma_rows(nv, lambda i: rows_ref.at[i], out_row, sem)


def local_moe_pallas(x, slot_to_token, slot_w, block_row, block_eid,
                     block_nvalid, w_in, w_gate, w_out, *,
                     activation: str = "swiglu", block_c: int,
                     block_f: int = 256, interpret: bool = False):
    """x: [T, d] tokens; slot_to_token: [S] int32 in [0, T] (``T`` marks
    empty slots, which only ever sit past a segment's valid count);
    slot_w: [S] float32; block vectors as in
    ``moe_gemm.kernel.grouped_ffn_ragged_pallas``.  Returns the [T, d]
    float32 combined output (cast at the caller)."""
    T, d = x.shape
    f = w_in.shape[-1]
    bc = block_c
    bf = min(block_f, f)
    nb = block_row.shape[0]
    nf = pl.cdiv(f, bf)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nb, nf),
        in_specs=[
            any_spec,                  # token rows, gathered by DMA
            pl.BlockSpec((1, d, bf), _fused_win_map),
            pl.BlockSpec((1, d, bf), _fused_win_map),
            pl.BlockSpec((1, bf, d), _fused_wout_map),
            any_spec,                  # zero output, aliased below
        ],
        out_specs=any_spec,
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32),
                        pltpu.VMEM((bc, d), jnp.float32),
                        pltpu.VMEM((bc, 1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA],
    )
    kernel = functools.partial(_fused_kernel, activation=activation,
                               block_c=bc)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, 1, d), jnp.float32),
        # operand 9 (after the five prefetch vectors) is the zero output
        input_output_aliases={9: 0},
        interpret=interpret,
    )(slot_to_token, slot_w.astype(jnp.float32), block_row, block_eid,
      block_nvalid, rows_view(x), w_in, w_gate, w_out,
      jnp.zeros((T, 1, d), jnp.float32))
    return out.reshape(T, d)


# ---------------------------------------------------------------------------
# analyzer layout (repro.analysis.pallas_check)
# ---------------------------------------------------------------------------


@backend.register_kernel("moe_fused.local_moe")
def _fused_layouts():
    """Canonical fused-megakernel layout.  The [T, d] output is revisited
    by *every* row block (its window is the whole array while the
    non-trailing grid dimension b varies) — the exact scatter-revisit
    pattern the analyzer requires ``acc_guarded`` for; the kernel earns
    the flag with its zero-initialized (aliased) output and per-row
    read-modify-write epilogue."""
    from repro.kernels.moe_gemm import ops

    E, T, d, f = 4, 128, 128, 512
    bf = 256
    seg_offsets = np.asarray([0, 128, 192, 320, 384], np.int32)
    seg_experts = np.arange(E, dtype=np.int32)
    bc, brow, beid, bseg, bloc = ops.plan_blocks(seg_offsets, seg_experts,
                                                 block_c=128)
    S = int(seg_offsets[-1])
    tok = np.arange(S, dtype=np.int32) % (T + 1)   # values in [0, T]
    slot_w = np.ones(S, np.float32)
    nv = np.full(brow.shape, bc, np.int32)
    grid = (brow.shape[0], f // bf)
    return [backend.KernelLayout(
        kernel="moe_fused.local_moe",
        grid=grid,
        prefetch=(tok, slot_w, brow, beid, nv),
        blocks=(
            backend.BlockDecl("x_rows", "in", 4, (T, 1, d), (T, 1, d),
                              _hbm_map, hbm=True),
            backend.BlockDecl("w_in", "in", 4, (1, d, bf), (E, d, f),
                              _fused_win_map),
            backend.BlockDecl("w_gate", "in", 4, (1, d, bf), (E, d, f),
                              _fused_win_map),
            backend.BlockDecl("w_out", "in", 4, (1, bf, d), (E, f, d),
                              _fused_wout_map),
            backend.BlockDecl("o", "out", 4, (T, 1, d), (T, 1, d),
                              _hbm_map, acc_guarded=True, hbm=True),
            backend.BlockDecl("acc", "scratch", 4, (bc, d)),
            backend.BlockDecl("xblk", "scratch", 4, (bc, d)),
            backend.BlockDecl("rows", "scratch", 4, (bc, 1, d)),
        ),
        meta={"block_c": int(bc), "seg_offsets": seg_offsets,
              "seg_experts": seg_experts, "block_seg": bseg,
              "block_loc": bloc},
    )]
