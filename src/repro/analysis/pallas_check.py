"""Static Pallas kernel analyzer.

Walks the kernel registry (``repro.kernels.backend.KERNEL_REGISTRY`` —
each kernel package registers ``KernelLayout`` declarations built from
the *same* index-map functions its ``pallas_call`` uses) and checks, per
layout, without executing anything:

* **vmem-budget** — the per-grid-step working set (every in/out block,
  double-buffered unless its index map is constant over the grid i.e.
  the block is resident, plus scratch) must fit the VMEM budget; operands
  left in HBM (``hbm``) take none.
* **index-bounds** — every block index the index maps produce over the
  *entire* grid (scalar-prefetch vectors included) must address a block
  inside the declared array shape.
* **plan-blocks** — layouts built over a ``plan_blocks`` decomposition
  (``meta`` carries the segment table) must satisfy its invariants: the
  block size divides every non-empty segment width, no row block
  straddles two segments, and each block's expert id matches its
  segment's.
* **scatter-race** — an output block revisited across a **non-trailing**
  grid dimension leaves the VMEM-resident window between visits; unless
  the kernel declares ``acc_guarded`` (zero-init + read-modify-write,
  the fused megakernel's scatter epilogue), the revisit silently
  clobbers earlier writes.  Revisits that only vary the trailing
  (sequential) dimension stay resident and are safe.
"""

from __future__ import annotations

import itertools
import math

from repro.analysis import Violation

# Per-core VMEM on current TPUs is ~16 MiB; kernels budget their working
# sets against it (see the moe_gemm docstring).
VMEM_BUDGET_BYTES = 16 * 2 ** 20


def _iter_grid(grid):
    return itertools.product(*(range(n) for n in grid))


def _block_indices(block, grid, prefetch):
    """Evaluate ``block.index_map`` over the whole grid; yields
    ``(grid_point, block_index_tuple)``."""
    for pt in _iter_grid(grid):
        idx = block.index_map(*pt, *prefetch)
        yield pt, tuple(int(i) for i in idx)


def _is_resident(block, grid, prefetch):
    """A block whose index map is constant over the grid is fetched once
    and stays resident (no double buffering)."""
    seen = {idx for _, idx in _block_indices(block, grid, prefetch)}
    return len(seen) == 1


def check_vmem(layout) -> list[Violation]:
    total = 0
    for b in layout.blocks:
        nbytes = math.prod(b.block_shape) * b.dtype_bytes
        if b.hbm:
            continue
        if b.kind == "scratch":
            total += nbytes
        else:
            resident = _is_resident(b, layout.grid, layout.prefetch)
            total += nbytes * (1 if resident else 2)  # double-buffered DMA
    if total > VMEM_BUDGET_BYTES:
        return [Violation(
            "pallas", "vmem-budget", layout.kernel,
            f"per-grid-step working set {int(total)} B exceeds the "
            f"{VMEM_BUDGET_BYTES} B VMEM budget")]
    return []


def check_index_bounds(layout) -> list[Violation]:
    out = []
    for b in layout.blocks:
        if b.kind == "scratch":
            continue
        nblocks = tuple(-(-a // s) for a, s in zip(b.array_shape,
                                                   b.block_shape))
        bad = None
        for pt, idx in _block_indices(b, layout.grid, layout.prefetch):
            if len(idx) != len(nblocks):
                bad = (pt, idx, "rank mismatch")
                break
            if any(i < 0 or i >= n for i, n in zip(idx, nblocks)):
                bad = (pt, idx, f"outside block bounds {nblocks}")
                break
        if bad is not None:
            pt, idx, why = bad
            out.append(Violation(
                "pallas", "index-bounds", f"{layout.kernel}:{b.name}",
                f"index map at grid point {pt} produced block index "
                f"{idx}: {why} (array {b.array_shape}, block "
                f"{b.block_shape})"))
    return out


def check_plan_blocks(layout) -> list[Violation]:
    meta = layout.meta
    if "seg_offsets" not in meta:
        return []
    out = []
    offs = [int(o) for o in meta["seg_offsets"]]
    experts = [int(e) for e in meta["seg_experts"]]
    bc = int(meta["block_c"])
    widths = [offs[s + 1] - offs[s] for s in range(len(offs) - 1)]
    for s, w in enumerate(widths):
        if w and w % bc:
            out.append(Violation(
                "pallas", "plan-blocks", layout.kernel,
                f"block size {bc} does not divide segment {s} width {w}"))
    # prefetch layout convention: the last three vectors are
    # (block_row, block_eid, block_nvalid) — see plan_blocks
    brow, beid = layout.prefetch[-3], layout.prefetch[-2]
    for b in range(len(brow)):
        start = int(brow[b]) * bc
        seg = None
        for s in range(len(widths)):
            if offs[s] <= start < offs[s + 1]:
                seg = s
                break
        if seg is None or start + bc > offs[seg + 1]:
            out.append(Violation(
                "pallas", "plan-blocks", layout.kernel,
                f"row block {b} (rows {start}:{start + bc}) straddles a "
                f"segment boundary"))
        elif int(beid[b]) != experts[seg]:
            out.append(Violation(
                "pallas", "plan-blocks", layout.kernel,
                f"row block {b} multiplies expert {int(beid[b])} but lies "
                f"in segment {seg} of expert {experts[seg]}"))
    return out


def check_scatter_race(layout) -> list[Violation]:
    out = []
    for b in layout.blocks:
        if b.kind != "out":
            continue
        visits = {}
        for pt, idx in _block_indices(b, layout.grid, layout.prefetch):
            visits.setdefault(idx, []).append(pt)
        for idx, pts in visits.items():
            nontrailing = {pt[:-1] for pt in pts}
            if len(nontrailing) > 1 and not b.acc_guarded:
                out.append(Violation(
                    "pallas", "scatter-race", f"{layout.kernel}:{b.name}",
                    f"output block {idx} is revisited across a "
                    f"non-trailing grid dimension (e.g. grid points "
                    f"{pts[0]} and {pts[-1]}) without an accumulation "
                    f"guard — earlier writes would be clobbered"))
                break
    return out


def check_layout(layout) -> list[Violation]:
    return (check_vmem(layout) + check_index_bounds(layout)
            + check_plan_blocks(layout) + check_scatter_race(layout))


def run(layouts=None) -> tuple[list[Violation], list[str]]:
    """Check every registered layout (or an explicit list, for fixtures).
    Returns ``(violations, covered_layout_names)``."""
    if layouts is None:
        # registration happens on import
        from repro.kernels import backend
        from repro.kernels.moe_fused import kernel as _f   # noqa: F401
        from repro.kernels.moe_gemm import kernel as _g    # noqa: F401
        from repro.kernels.moe_permute import kernel as _p # noqa: F401
        layouts = [lay for lays in backend.registered_layouts().values()
                   for lay in lays]
    violations, covered = [], []
    for lay in layouts:
        covered.append(lay.kernel)
        violations.extend(check_layout(lay))
    return violations, covered
