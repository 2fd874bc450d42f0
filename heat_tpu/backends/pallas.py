"""Single-device Pallas backend.

The hand-written-kernel variant: analog of the reference's explicit CUDA
Fortran kernel (fortran/cuda_kernel/heat.F90) and the HIP C++ kernels
(fortran/hip/heat_kernel.cpp). Shares the chunked driver with the XLA
backend; only the per-step kernel differs. Arbitrary grid shapes run
through the kernel via internal alignment padding; only f64 (unsupported on
the TPU vector unit) falls back to the XLA step.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np

from ..config import HeatConfig
from ..ops.pallas_stencil import (
    ftcs_multistep_edges_pallas,
    ftcs_multistep_ghost_pallas,
    ftcs_multistep_periodic_pallas,
    ftcs_step_edges_pallas,
    ftcs_step_ghost_pallas,
    ftcs_step_periodic_pallas,
    multistep_points_bc,
)
from ..ops.stencil import run_steps
from . import SolveResult, register
from .common import drive, resolve_initial_field

# default temporal-blocking depth: amortizes the kernel's per-pass HBM
# traffic over 16 steps (measured throughput on v5e is flat past 16); the
# kernels chunk internally if asked for more than a pass affords
_AUTO_FUSE = 16


def fuse_depth(cfg: HeatConfig) -> int:
    if cfg.fuse_steps:
        return cfg.fuse_steps
    if cfg.dtype != "float64":
        return _AUTO_FUSE  # 3D chunks itself down to what VMEM affords
    return 1


def make_advance(cfg: HeatConfig):
    r = cfg.r
    bc_value = cfg.bc_value
    kf = fuse_depth(cfg)

    if cfg.bc == "edges":
        step = lambda t: ftcs_step_edges_pallas(t, r)
        multi = lambda t, k: ftcs_multistep_edges_pallas(t, r, k)
    elif cfg.bc == "periodic":
        step = lambda t: ftcs_step_periodic_pallas(t, r)
        multi = lambda t, k: ftcs_multistep_periodic_pallas(t, r, k)
    else:
        step = lambda t: ftcs_step_ghost_pallas(t, r, bc_value)
        multi = lambda t, k: ftcs_multistep_ghost_pallas(t, r, bc_value, k)

    # Two kernel passes per loop iteration. A pass cannot step in place
    # (each row tile reads its neighbours' halo rows), so it writes a fresh
    # buffer; with one pass a body, XLA copies that buffer back into the
    # loop carry after every pass. With two, the first pass's input is dead
    # when the second writes, so the second writes the carry's own buffer.
    # An odd pass count costs one field copy a call, before the loop.
    def two_passes(i, t):
        return multi(multi(t, kf), kf)

    @functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
    def advance(T, k: int):
        n_fused, rem = divmod(k, kf) if kf > 1 else (0, k)
        if n_fused % 2:
            T = multi(T, kf)
        if n_fused // 2:
            T = jax.lax.fori_loop(0, n_fused // 2, two_passes, T)
        return run_steps(T, rem, step)

    # drive() counts the kernels' computed point-steps per chunk from this
    advance.points_computed = functools.partial(kernel_points, cfg)
    return advance


def kernel_points(cfg: HeatConfig, k: int) -> Optional[int]:
    """Point-steps the Pallas kernels compute for ``advance(T, k)``: the
    fused passes plus the single-step remainder, each counted by the
    kernels' own plan. None where XLA does the stepping (float64)."""
    kf = fuse_depth(cfg)
    n_fused, rem = divmod(k, kf) if kf > 1 else (0, k)
    total = 0
    for depth, times in ((kf, n_fused), (1, rem)):
        if times:
            p = multistep_points_bc(cfg.shape, cfg.dtype, cfg.bc, depth)
            if p is None:
                return None
            total += times * p
    return total


@register("pallas")
def solve(cfg: HeatConfig, T0: Optional[np.ndarray] = None,
          fetch: bool = True, warm_exec: bool = False,
          two_point_repeats: int = 0, **_) -> SolveResult:
    T, start_step = resolve_initial_field(cfg, T0)
    return drive(cfg, T, make_advance(cfg), start_step=start_step, fetch=fetch,
                 warm_exec=warm_exec, two_point_repeats=two_point_repeats)
