"""Shared Pallas backend policy for the kernel packages.

One policy, three consumers (``moe_permute``, ``moe_gemm``, ``moe_fused``)
— keeping it in a single module means the permute, GEMM, and fused layers
of the same engine call can never drift onto different backends:

* ``want_pallas(None)`` (auto) resolves to the Pallas kernels on
  accelerators (TPU/GPU) and the jnp references elsewhere;
  ``REPRO_KERNEL_INTERPRET=1`` additionally flips the auto default on, so
  CPU-only CI executes the kernel bodies under the interpreter.
* ``pallas_viable()``: TPU compiles through Mosaic; CPU runs
  ``interpret=True``; GPU has no Mosaic/Triton lowering for the
  scalar-prefetch grids these kernels use, so the reference path is used
  even when the flag is on.
* ``kernels_active(flag)`` — the one decision every public kernel entry
  keys on: ``want_pallas(flag) and pallas_viable()``.
* ``interpret_mode()``: everything that is not a real TPU interprets.

The module also hosts the **kernel registry** consumed by the static
analyzer (``repro.analysis.pallas_check``): each kernel package registers
a builder that re-states its grid / BlockSpec layout as ``KernelLayout``
declarations over canonical shapes, sharing the *same* index-map
functions the real ``pallas_call`` uses so the declaration cannot drift
from the kernel.
"""

from __future__ import annotations

import dataclasses
import os
from collections.abc import Callable, Sequence

import jax
import numpy as np

_ENV_TRUE = ("1", "true")
_ENV_FALSE = ("0", "false")


def use_pallas_default() -> bool:
    """The engine's auto policy: Pallas on accelerators, ref elsewhere."""
    return jax.default_backend() in ("tpu", "gpu")


def env_interpret() -> bool:
    """Strictly-parsed ``REPRO_KERNEL_INTERPRET``: 1/true -> on, 0/false
    (or unset) -> off, anything else raises.  A typo'd value used to be
    silently ignored, leaving CI on the jnp reference path while claiming
    to exercise the kernel bodies."""
    raw = os.environ.get("REPRO_KERNEL_INTERPRET")
    if raw is None:
        return False
    val = raw.strip().lower()
    if val in _ENV_TRUE:
        return True
    if val in _ENV_FALSE:
        return False
    raise ValueError(
        f"REPRO_KERNEL_INTERPRET={raw!r} is not a recognized value; "
        f"use one of {_ENV_TRUE + _ENV_FALSE}")


def want_pallas(use_pallas=None) -> bool:
    if use_pallas is None:
        return use_pallas_default() or env_interpret()
    return bool(use_pallas)


def pallas_viable() -> bool:
    return jax.default_backend() in ("tpu", "cpu")


def kernels_active(use_pallas=None) -> bool:
    """Whether the Pallas entries actually run for this ``use_pallas`` flag
    (vs the jnp references).  The dispatch engine keys the occupancy
    machinery (valid-count exchange, ragged/fused compute) off this."""
    return want_pallas(use_pallas) and pallas_viable()


def interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def float0(a):
    """Symbolic-zero cotangent for integer operands of a custom_vjp."""
    return np.zeros(a.shape, jax.dtypes.float0)


# ---------------------------------------------------------------------------
# kernel registry (consumed by repro.analysis.pallas_check)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockDecl:
    """One operand of a ``pallas_call``, as the analyzer sees it.

    ``index_map`` is the *same function object* the kernel's BlockSpec
    uses, called with ``(*grid_ids, *prefetch)`` where the prefetch
    vectors are numpy arrays — the analyzer evaluates it over the whole
    grid to bound-check the block indices it produces.  ``kind`` is one
    of ``"in"`` / ``"out"`` / ``"scratch"`` (scratch has no array shape
    or index map).  ``acc_guarded`` declares that revisits of the same
    output block across a non-trailing grid dimension are protected by a
    zero-init + read-modify-write accumulation (the fused megakernel's
    scatter pattern); the analyzer rejects unguarded revisits.  ``hbm``
    marks an operand left in HBM (``memory_space=pl.ANY``) whose rows the
    kernel moves by DMA: its window is the whole array and it takes no
    VMEM.
    """

    name: str
    kind: str
    dtype_bytes: float
    block_shape: tuple[int, ...]
    array_shape: tuple[int, ...] | None = None
    index_map: Callable[..., tuple[int, ...]] | None = None
    acc_guarded: bool = False
    hbm: bool = False


@dataclasses.dataclass(frozen=True)
class KernelLayout:
    """A concrete grid/BlockSpec instantiation of one kernel.

    ``prefetch`` holds the scalar-prefetch vectors (numpy) fed to every
    block's ``index_map``; ``meta`` carries kernel-specific invariants
    the analyzer cross-checks (e.g. the ``plan_blocks`` segment table
    behind a ragged layout's block vectors).
    """

    kernel: str
    grid: tuple[int, ...]
    blocks: tuple[BlockDecl, ...]
    prefetch: tuple = ()
    meta: dict = dataclasses.field(default_factory=dict)


KERNEL_REGISTRY: dict[str, Callable[[], Sequence[KernelLayout]]] = {}


def register_kernel(name: str):
    """Register a layout builder under ``name``.  Builders take no
    arguments and return the kernel's canonical ``KernelLayout``s (one
    per representative shape family)."""

    def deco(fn):
        KERNEL_REGISTRY[name] = fn
        return fn

    return deco


def registered_layouts() -> dict[str, Sequence[KernelLayout]]:
    """Materialize every registered builder (importing the kernel
    packages is the caller's job — registration happens on import)."""
    return {name: tuple(build()) for name, build in
            sorted(KERNEL_REGISTRY.items())}
