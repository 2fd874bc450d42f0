"""Hand-written Pallas TPU stencil kernels.

The TPU equivalent of the reference's explicit device kernels: CUDA Fortran
``heat_equation`` (fortran/cuda_kernel/heat.F90:39-54), HIP C++ ``heat_eqn``
(fortran/hip/heat_kernel.cpp:31-45), and the Jinja2-JIT CUDA C kernel
(python/cuda/cuda.py:58-86). Where those tile the grid into 32x8 / 128x4
thread blocks, this kernel tiles rows into VMEM-resident blocks aligned to
the 8x128 VPU lanes and streams them HBM->VMEM->HBM through Pallas's
pipelined grid.

Design notes:
- Grid is 1-D over row tiles; each program sees its own tile plus a
  ``kpad``-row halo slab above and below (three BlockSpecs on the same
  array: two thin halo blocks + the main tile), supplying the row halo the
  reference fetches via its ghost ring. Column neighbors are in-tile lane
  rotates (full rows live in the block).
- **Temporal blocking**: the kernel runs ``ksteps`` FTCS steps per HBM
  pass. One pass costs ~(1 + 2k/tile)*8 bytes/point; fusing k steps
  amortizes to ~8/k B/point/step — the stencil analog of kernel fusion that
  the reference's one-kernel-launch-per-step model cannot express
  (fortran/cuda_kernel/heat.F90:30-34). Valid because a point's k-step
  dependency cone spans <= k < kpad halo rows, and neighbor shifts are
  wrap-around rotates whose band-edge corruption also travels only one row
  per mini-step — it never reaches the center tile while k <= kpad.
- Boundary cells are frozen by a *mask-multiplied* update
  (``band + mask*r*lap`` with mask=0 on the boundary ring), the
  multiplicative form of the reference's in-kernel interior guard
  ``i/=1 .and. i/=ngrid`` (fortran/cuda_kernel/heat.F90:49). Frozen cells
  never change, so no pristine copy of the input band needs to stay live
  across the fused mini-steps (that retained copy was the old kernel's
  VMEM-pressure ceiling).
- **Arbitrary shapes**: inputs are padded to lane/tile alignment inside the
  wrapper; padding cells are frozen (never read by logical cells beyond the
  frozen logical boundary) and cropped on return.
- The runtime constant ``r`` is baked into the kernel as a closure constant
  — the Pallas analog of the reference's Jinja2 constant-baking
  (python/cuda/cuda.py:85), with jit retrace standing in for re-render.
- bf16 bands upcast to f32 once on load and round once at the store
  ("bf16 stencil + fp32 accumulate" mode).
- The Dirichlet-by-ghost ("ghost") BC is the same kernel on a bc-padded
  array whose frozen ring IS the ghost ring.

Measured on a single v5e chip (4096^2 f32): ~26 Gpts/s for the fused-XLA
step, ~128 Gpts/s for this kernel at ksteps=16 — 2.5x the 16 B/pt naive
roofline that one-step-per-pass designs (the reference's) are bound by.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import machine
from .stencil import (accum_dtype_for, ftcs_step_edges, ftcs_step_ghost,
                      ftcs_step_periodic)

# Chip-dependent constants (VMEM ceilings, band budgets, fitted op/HBM
# rates for the cost models) live in heat_tpu.machine, selected by
# device_kind — v5e values are measured, other chips spec-derived. The
# derivation notes for the v5e numbers:
# - vmem_limit 110 MiB (of the chip's 128): the 3D plan's 512^3
#   (64,64,k=8) winner measures 102.05 MiB scoped demand; a 100 MiB
#   ceiling rejects it at compile time (the planner's _fits_vmem estimate
#   runs ~20 MiB below Mosaic's true stack demand).
# - band_budget 12 MiB: 6 MiB caps 32768^2 bf16 at 69 Gpts/s (16-row
#   tiles, 3x halo overhead); 12 MiB doubles it to 135 Gpts/s.
# - vpu_ops 2.2e12: backed out of overhead-corrected on-chip runs (rolled
#   col-tiled bf16 32768^2 at 512x4096 = 1.89e11 pts/s x ~12.4 ops/pt
#   ~= 2.3e12; thin-band 4096^2 f32 ~= 2.0e12; midpoint).
# - ops_rate_3d 2.86e12: fit from the 512^3 sweep with ADDITIVE
#   compute+bandwidth cost (max() mispicked k=2 at 68% roofline over k=8
#   at 112%); (R=64,M=64) k=4/k=8 rates match within 1%.
# - coltiled_band_cap 10 MiB: bands past it send Mosaic compiles from
#   ~1 min (256-row tiles) to 5 min (512) to >12 min (1024 rows).
_chip = machine.current

# per-pass fusion cap: halo rows (and compile-time unroll) stay bounded;
# measured throughput is flat past 16. Architectural (dependency-cone /
# unroll bound), not a per-chip rate — stays module-level.
_KMAX_2D = 32
# 3D per-pass fusion cap: the (row,mid)-tiled kernel's band pays a 2k
# margin on BOTH non-lane axes, so deep unrolls blow the VMEM band budget
# much earlier than in 2D — the _plan_3d search never considers k > 8
_KMAX_3D = 8


def _sublane(dtype) -> int:
    return 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8

def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult

_FORCE_COMPILED = False


def _interpret() -> bool:
    if _FORCE_COMPILED:
        return False
    return jax.default_backend() != "tpu"


import contextlib  # noqa: E402


@contextlib.contextmanager
def force_compiled_kernels():
    """Trace Pallas calls as real Mosaic custom calls even off-TPU.

    For AOT *topology* compiles: ``jax.experimental.topologies`` lets a
    CPU-only host compile a genuine multi-chip TPU executable (the Mosaic
    compiler ships with libtpu and needs no attached device), which is
    how benchmarks/topology_schedule.py extracts real multi-chip TPU
    schedules — async collective-permute pairs and all — without a
    chip. Clears jit caches on entry/exit: interpret-mode tracings of
    the same call signature share cache keys with compiled ones."""
    global _FORCE_COMPILED
    jax.clear_caches()
    _FORCE_COMPILED = True
    try:
        yield
    finally:
        _FORCE_COMPILED = False
        jax.clear_caches()


# --------------------------------------------------------------------------
# 2D: halo-slab BlockSpecs, rotate shifts, masked multiplicative update
# --------------------------------------------------------------------------


def _halo_2d(ksteps: int, dtype) -> int:
    """Halo slab height: >= ksteps (dependency cone), sublane-aligned."""
    return _round_up(max(ksteps, 1), _sublane(dtype))


# thin-band deep-unroll compile cap (round 4): the 32-step unrolled thin
# kernel on a ~10 MiB band (8320-wide rows, the 8192-local shard family)
# sent Mosaic/LLVM into a >36-min compile, observed live and killed,
# while narrow bands (5.4 MiB, 4224-wide — the headline 4096^2 shape)
# compile at k=32 in ~1 min on chip. Above this band size, thin passes
# chunk at 16 instead of _KMAX_2D. Per-k curves:
# benchmarks/compile_bisect_topology*.json (the bisect pins
# local_kernel="pallas" — off-TPU "auto" measures the XLA program).
_THIN_DEEP_BAND_CAP_BYTES = 6 * 1024 * 1024


def _thin_chunk_cap(n_pad: int, dtype_str) -> int:
    """Max per-pass unroll for the thin-band kernel at this row width —
    the compile-sanity analog of the chip table's coltiled band cap."""
    kpad = _halo_2d(_KMAX_2D, dtype_str)
    tile = _tile_2d(n_pad, kpad)
    band = (tile + 2 * kpad) * n_pad * 4
    return 16 if band > _THIN_DEEP_BAND_CAP_BYTES else _KMAX_2D


def effective_chunk_2d(shape, dtype_str, ksteps: int | None = None) -> int:
    """Per-pass chunk depth of the kernel ``_plan_2d`` SELECTS at a
    LOGICAL runtime shape — the shape the kernel will actually see,
    ghosts included. The one derivation callers outside the planner (the
    sharded fuse chooser, the compile guard) may use: re-deriving the
    padding recipe in another module is how the round-5 near-threshold
    bug happened (cap computed on the unpadded width while the kernel
    ran on the ghost-padded one), and hardcoding the THIN cap would pin
    the exchange depth to the wrong kernel when the planner picks the
    coltiled body (its plan carries its own kchunk)."""
    plan = _plan_2d(tuple(shape), dtype_str,
                    _KMAX_2D if ksteps is None else ksteps)
    return plan[1] if plan[0] == "thin" else plan[-1]


def _pass_depths(ksteps: int, cap: int) -> list:
    """Fusion depth of each kernel pass when ``ksteps`` steps run in passes
    of at most ``cap``: the one chunking every multistep loop here uses."""
    full, rem = divmod(ksteps, cap)
    return [cap] * full + ([rem] if rem else [])


def _thin_geometry(m: int, n: int, ksteps: int, dtype):
    """(n_pad, kpad, tile, m_pad) of one thin-band pass of ``ksteps`` steps
    over an (m, n) array: the one derivation ``_pallas_2d`` and
    ``multistep_points`` share."""
    n_pad = _round_up(max(n, 128), 128)
    kpad = _halo_2d(ksteps, dtype)
    tile = _tile_2d(n_pad, kpad)
    return n_pad, kpad, tile, _round_up(max(m, tile), tile)


def _tile_2d(n_pad: int, kpad: int) -> int:
    """Row-tile height: a multiple of kpad (so halo blocks index evenly),
    sized to keep the (tile + 2*kpad)-row band near the budget (the band is
    held in the f32 accumulation dtype regardless of storage dtype)."""
    cap = _chip().band_budget_bytes // (n_pad * 4) - 2 * kpad
    tile = min(256, max(cap, kpad))
    return max(kpad, (tile // kpad) * kpad)


def cost_thin_2d(n_pad: int, kchunk: int, dtype_str, chip) -> float:
    """Modeled seconds per point-step for the thin-band kernel at chunk
    depth ``kchunk`` — additive compute + bandwidth (measured: the two
    don't overlap enough for max(); see the ops_rate_3d note). THE cost
    model ``_plan_2d`` ranks with, exposed at module level so
    ``heat_tpu.calibrate`` inverts the planner's actual model (not a
    hand-copied formula that drifts)."""
    item = jnp.dtype(dtype_str).itemsize
    kpad = _halo_2d(kchunk, dtype_str)
    tile = _tile_2d(n_pad, kpad)
    compute = 11.0 * (tile + 2 * kpad) / tile / chip.vpu_ops_per_s
    bw = (2.0 * tile + 2 * kpad) * item / (tile * kchunk) / chip.hbm_bytes_per_s
    return compute + bw


def cost_3d(R: int, M: int, k: int, dtype_str, chip) -> float:
    """Modeled seconds per COMPUTED point-step for the (row, mid)-tiled 3D
    kernel at geometry (R, M, k) — callers apply the alignment-padding
    waste factor for logical points. Shared by ``_plan_3d`` and
    ``heat_tpu.calibrate`` (same no-drift contract as cost_thin_2d)."""
    item = jnp.dtype(dtype_str).itemsize
    km = _round_up(k, _sublane(dtype_str))
    band = (R + 2 * k) * (M + 2 * km)
    tile = R * M
    compute = 13.0 * band / tile / chip.ops_rate_3d
    bw = (band + tile) * item / (tile * k) / chip.hbm_bytes_per_s
    return compute + bw


def _make_kernel_2d(r: float, tile: int, kpad: int, n_pad: int, ksteps: int):
    """Kernel body. ``bounds_ref`` is an SMEM (1,4) i32 array
    [row_lo, row_hi, col_lo, col_hi]: cells with index <= lo or >= hi on
    either axis are frozen. For a plain solve that is the boundary ring
    (0, m-1, 0, n-1); the sharded backend passes per-shard values so only
    global-domain edges freeze (see ftcs_multistep_bounded_pallas)."""
    rows = tile + 2 * kpad

    def kernel(bounds_ref, prev_ref, cur_ref, next_ref, out_ref):
        i = pl.program_id(0)
        store_dt = out_ref.dtype
        acc_dt = accum_dtype_for(store_dt)
        band = jnp.concatenate(
            [prev_ref[:], cur_ref[:], next_ref[:]], axis=0
        ).astype(acc_dt)
        grow = i * tile - kpad + jax.lax.broadcasted_iota(
            jnp.int32, (rows, n_pad), 0
        )
        gcol = jax.lax.broadcasted_iota(jnp.int32, (rows, n_pad), 1)
        frozen = (
            (grow <= bounds_ref[0, 0]) | (grow >= bounds_ref[0, 1])
            | (gcol <= bounds_ref[0, 2]) | (gcol >= bounds_ref[0, 3])
        )
        maskr = jnp.where(frozen, 0.0, r).astype(acc_dt)

        for _ in range(ksteps):  # static unroll
            up = pltpu.roll(band, 1, 0)
            dn = pltpu.roll(band, rows - 1, 0)
            lf = pltpu.roll(band, 1, 1)
            rt = pltpu.roll(band, n_pad - 1, 1)
            # solo band is NaN-free by construction (no foreign lanes);
            # the multiplicative freeze is the reference's interior guard
            # heat-tpu: allow[mosaic-kernel-safety] solo NaN-free freeze
            band = band + maskr * (up + dn + lf + rt - 4.0 * band)
        out_ref[:] = band[kpad : kpad + tile].astype(store_dt)

    return kernel


@functools.partial(jax.jit, static_argnames=("r", "ksteps"))
def _pallas_2d(T: jax.Array, r: float, ksteps: int,
               bounds: jax.Array | None = None) -> jax.Array:
    """``ksteps`` FTCS steps on an arbitrary 2D array, freezing cells at or
    beyond ``bounds`` (default: the boundary ring — "edges" semantics).
    ksteps must not exceed _KMAX_2D (callers chunk; see _multistep)."""
    assert ksteps <= _KMAX_2D, (
        f"ksteps={ksteps} exceeds _KMAX_2D={_KMAX_2D}; chunk via _multistep "
        f"(unbounded fusion inflates compile time and VMEM)")
    m, n = T.shape
    if bounds is None:
        bounds = jnp.asarray([[0, m - 1, 0, n - 1]], jnp.int32)
        # with the boundary ring frozen, garbage in the clamped out-of-range
        # halo blocks of the first/last grid step is only read by frozen
        # rows; custom bounds callers own a discard margin >= ksteps instead
    bounds = bounds.reshape(1, 4).astype(jnp.int32)
    n_pad, kpad, tile, m_pad = _thin_geometry(m, n, ksteps, T.dtype)
    assert ksteps <= kpad <= tile and tile % kpad == 0
    padded = (m_pad != m) or (n_pad != n)
    Tp = jnp.pad(T, ((0, m_pad - m), (0, n_pad - n))) if padded else T
    grid = (m_pad // tile,)
    ratio = tile // kpad
    nhblk = m_pad // kpad
    smem = pl.BlockSpec((1, 4), lambda i: (0, 0), memory_space=pltpu.SMEM)
    halo = lambda imap: pl.BlockSpec((kpad, n_pad), imap, memory_space=pltpu.VMEM)
    main = lambda imap: pl.BlockSpec((tile, n_pad), imap, memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _make_kernel_2d(float(r), tile, kpad, n_pad, ksteps),
        out_shape=jax.ShapeDtypeStruct(Tp.shape, Tp.dtype),
        grid=grid,
        name="heat_ftcs_2d",
        in_specs=[
            smem,
            halo(lambda i: (jnp.maximum(i * ratio - 1, 0), 0)),
            main(lambda i: (i, 0)),
            halo(lambda i: (jnp.minimum((i + 1) * ratio, nhblk - 1), 0)),
        ],
        out_specs=main(lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_chip().vmem_limit_bytes,
        ),
        cost_estimate=pl.CostEstimate(
            flops=9 * (tile + 2 * kpad) * grid[0] * n_pad * ksteps,
            bytes_accessed=(2 * m_pad + 2 * kpad * grid[0]) * n_pad
            * Tp.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=_interpret(),
    )(bounds, Tp, Tp, Tp)
    return out[:m, :n] if padded else out


# --------------------------------------------------------------------------
# two-axis tiling (3x3 halo-block scheme): shared planning machinery
#
# The thin-band 2D kernel above tiles rows only; its band must span the full
# row width, so very wide arrays (bf16 32768^2: 128 KiB/row) afford few rows
# per band and the halo fraction balloons (round-1: 1.5x redundant compute).
# The 3D kernel has the same disease worse: whole (mid, n) planes as halo.
# Cure for both: tile a second axis too, fetching a 3x3 neighborhood of
# blocks (4 corners + 4 edges + center) so halo volume scales with the tile
# surface. Mini-steps use shrinking slices (the valid region loses one cell
# per side per step) instead of full-band rotates — on the non-lane axes a
# shifted slice is an addressing offset, not a data permute.
# --------------------------------------------------------------------------


# cost-model rates and caps come from the per-chip table (see the
# derivation block at the top of this module); the planner caches below
# embed them, so machine.override() must flush those caches — they
# register with machine.register_cache at the bottom of this module


def _fits_vmem(band_cells: int, tile_cells: int, item: int) -> bool:
    # VMEM feasibility for the 3x3 scheme: double-buffered in/out blocks in
    # the storage dtype + the assembled band and its mini-step temporaries
    # in the accumulation dtype must fit under the Mosaic limit w/ headroom
    pipeline = 2 * (band_cells + tile_cells) * item
    working = 3 * band_cells * 4  # band + ~2 live temps, accumulation dtype
    return pipeline + working <= _chip().vmem_fit_bytes


def _grid_specs_3x3(shape_blocks, halo_blocks, nblocks, extra_dims):
    """BlockSpecs for the 3x3 neighborhood fetch over a (gi, gj) grid.

    ``shape_blocks`` = (tile_i, tile_j), ``halo_blocks`` = (k_i, k_j) block
    sizes; ``nblocks`` = (#halo-granularity blocks per axis) for clamping;
    ``extra_dims`` = trailing full-extent dims (3D: the lane axis).
    """
    (Ti, Tj), (ki, kj) = shape_blocks, halo_blocks
    ri, rj = Ti // ki, Tj // kj
    ni, nj = nblocks
    ext = tuple(extra_dims)
    zeros = (0,) * len(ext)

    def icl(i):
        return jnp.clip(i, 0, ni - 1)

    def jcl(j):
        return jnp.clip(j, 0, nj - 1)

    def bs(shape, imap):
        return pl.BlockSpec(shape + ext, imap, memory_space=pltpu.VMEM)

    return [
        bs((ki, kj), lambda i, j: (icl(i * ri - 1), jcl(j * rj - 1)) + zeros),
        bs((ki, Tj), lambda i, j: (icl(i * ri - 1), j) + zeros),
        bs((ki, kj), lambda i, j: (icl(i * ri - 1), jcl((j + 1) * rj)) + zeros),
        bs((Ti, kj), lambda i, j: (i, jcl(j * rj - 1)) + zeros),
        bs((Ti, Tj), lambda i, j: (i, j) + zeros),
        bs((Ti, kj), lambda i, j: (i, jcl((j + 1) * rj)) + zeros),
        bs((ki, kj), lambda i, j: (icl((i + 1) * ri), jcl(j * rj - 1)) + zeros),
        bs((ki, Tj), lambda i, j: (icl((i + 1) * ri), j) + zeros),
        bs((ki, kj), lambda i, j: (icl((i + 1) * ri), jcl((j + 1) * rj)) + zeros),
    ], bs((Ti, Tj), lambda i, j: (i, j) + zeros)


def _assemble_band(refs, acc_dt):
    """Concatenate the 3x3 fetched blocks into one band, rows x mids."""
    rows = [jnp.concatenate([refs[3 * g][:], refs[3 * g + 1][:],
                             refs[3 * g + 2][:]], axis=1) for g in range(3)]
    return jnp.concatenate(rows, axis=0).astype(acc_dt)


# --------------------------------------------------------------------------
# 3D: (row, mid)-tiled kernel, lane axis full-extent
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _plan_3d(shape, dtype_str, ksteps: int):
    """Choose ((m_pad, mid_pad, n_pad), R, M, kchunk) for the tiled 3D
    kernel: minimize (compute + bandwidth) per LOGICAL point-step —
    additive, not max(): measured, the two don't overlap enough (see
    machine.ops_rate_3d derivation note at the top of this
    module) — scaled by the alignment-padding waste factor.
    Ops/pt-step ~ 13 x band/tile area ratio (2 lane rotates + 2
    sublane-shifted reads + ~9 arithmetic; row-axis neighbor reads are
    addressing offsets)."""
    m, mid, n = shape
    sub = _sublane(dtype_str)
    n_pad = _round_up(max(n, 128), 128)
    item = jnp.dtype(dtype_str).itemsize
    chip = _chip()
    best = None
    for k in range(1, min(max(ksteps, 1), _KMAX_3D) + 1):
        km = _round_up(k, sub)
        for R in (8, 16, 32, 48, 64, 96, 128):
            if R % k:
                R = _round_up(R, k)
            R = min(R, _round_up(max(m, k), k))
            for M in (sub, 32, 64, 96, 128, 192):
                M = _round_up(M, km)
                M = min(M, _round_up(max(mid, km), km))
                band = (R + 2 * k) * (M + 2 * km)
                tile = R * M
                if not _fits_vmem(band * n_pad, tile * n_pad, item):
                    continue
                # cost per LOGICAL point: alignment padding is computed then
                # discarded (R=70 on a 512-row grid pads 9% dead rows)
                pad = (_round_up(max(m, R), R) * _round_up(max(mid, M), M)
                       / max(m * mid, 1))
                # ADDITIVE cost (cost_3d; measured: compute and HBM
                # streaming do not overlap enough for max() — see the
                # ops_rate_3d note); ties break toward deeper fusion
                key = (cost_3d(R, M, k, dtype_str, chip) * pad, band, -k)
                if best is None or key < best[0]:
                    best = (key, R, M, k)
    if best is None:
        # lane extent so large no (R, M, k) band fits VMEM: no kernel plan —
        # pallas_available() reports False and callers take the XLA path
        return None
    _, R, M, k = best
    m_pad = _round_up(max(m, R), R)
    mid_pad = _round_up(max(mid, M), M)
    return (m_pad, mid_pad, n_pad), R, M, k


def _make_kernel_3d(r: float, R: int, M: int, k: int, km: int, n_pad: int,
                    ksteps: int):
    """(row, mid)-tiled 3D body; ``bounds_ref`` is SMEM (1,6) i32
    [row_lo, row_hi, mid_lo, mid_hi, col_lo, col_hi] (see 2D). Mini-steps
    shrink the valid region by one cell per side (rows/mids); lane
    neighbors are wrap rotates whose band-edge garbage is confined the
    same way as the thin-band kernel's."""
    rows = R + 2 * k
    mids = M + 2 * km

    def kernel(bounds_ref, *refs):
        i = pl.program_id(0)
        j = pl.program_id(1)
        out_ref = refs[-1]
        store_dt = out_ref.dtype
        acc_dt = accum_dtype_for(store_dt)
        band = _assemble_band(refs[:9], acc_dt)

        bshape = (rows, mids, n_pad)
        grow = i * R - k + jax.lax.broadcasted_iota(jnp.int32, bshape, 0)
        gmid = j * M - km + jax.lax.broadcasted_iota(jnp.int32, bshape, 1)
        gcol = jax.lax.broadcasted_iota(jnp.int32, bshape, 2)
        frozen = (
            (grow <= bounds_ref[0, 0]) | (grow >= bounds_ref[0, 1])
            | (gmid <= bounds_ref[0, 2]) | (gmid >= bounds_ref[0, 3])
            | (gcol <= bounds_ref[0, 4]) | (gcol >= bounds_ref[0, 5])
        )
        maskr = jnp.where(frozen, 0.0, r).astype(acc_dt)

        cur = band
        for s in range(ksteps):  # static unroll, shrinking shapes
            # rolls are on the full lane axis only; the shrink is on the
            # non-lane axes with alignment held by construction, proven
            # by the chipless v5e compile labs (benchmarks/chip_check)
            # heat-tpu: allow[mosaic-kernel-safety] lane-axis-only rolls
            lf = pltpu.roll(cur, 1, 2)
            rt = pltpu.roll(cur, n_pad - 1, 2)
            ctr = cur[1:-1, 1:-1, :]
            lap = (cur[2:, 1:-1, :] + cur[:-2, 1:-1, :]
                   + cur[1:-1, 2:, :] + cur[1:-1, :-2, :]
                   + lf[1:-1, 1:-1, :] + rt[1:-1, 1:-1, :] - 6.0 * ctr)
            m_s = maskr[s + 1: rows - s - 1, s + 1: mids - s - 1, :]
            # solo band is NaN-free by construction (reference form)
            # heat-tpu: allow[mosaic-kernel-safety] solo NaN-free freeze
            cur = ctr + m_s * lap
        out_ref[:] = jax.lax.slice(
            cur, (k - ksteps, km - ksteps, 0),
            (k - ksteps + R, km - ksteps + M, n_pad)).astype(store_dt)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("r", "ksteps", "kplan", "logical_shape"))
def _pallas_3d_aligned(Tp: jax.Array, r: float, ksteps: int, kplan: int,
                       logical_shape, bounds: jax.Array | None = None) -> jax.Array:
    """``ksteps`` FTCS steps on an already tile-aligned 3D array whose
    logical (unpadded) extents are ``logical_shape``. ``kplan`` fixes the
    block geometry across chunks; a remainder pass may run ksteps < kplan.
    Callers chunk — see _multistep."""
    (m_pad, mid_pad, n_pad), R, M, kp = _plan_3d(
        logical_shape, str(Tp.dtype), kplan)
    assert Tp.shape == (m_pad, mid_pad, n_pad), (Tp.shape, m_pad, mid_pad, n_pad)
    assert kp == kplan and ksteps <= kplan
    sub = _sublane(Tp.dtype)
    km = _round_up(kplan, sub)
    m, mid, n = logical_shape
    if bounds is None:
        bounds = jnp.asarray([[0, m - 1, 0, mid - 1, 0, n - 1]], jnp.int32)
    bounds = bounds.reshape(1, 6).astype(jnp.int32)
    grid = (m_pad // R, mid_pad // M)
    smem = pl.BlockSpec((1, 6), lambda i, j: (0, 0), memory_space=pltpu.SMEM)
    in_specs, out_spec = _grid_specs_3x3(
        (R, M), (kplan, km), (m_pad // kplan, mid_pad // km), (n_pad,))
    band = (R + 2 * kplan) * (M + 2 * km)
    return pl.pallas_call(
        _make_kernel_3d(float(r), R, M, kplan, km, n_pad, ksteps),
        out_shape=jax.ShapeDtypeStruct(Tp.shape, Tp.dtype),
        grid=grid,
        name="heat_ftcs_3d",
        in_specs=[smem] + in_specs,
        out_specs=out_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_chip().vmem_limit_bytes,
        ),
        cost_estimate=pl.CostEstimate(
            flops=13 * band * n_pad * grid[0] * grid[1] * ksteps,
            bytes_accessed=(band + R * M) * n_pad * grid[0] * grid[1]
            * Tp.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=_interpret(),
    )(bounds, *([Tp] * 9))


# --------------------------------------------------------------------------
# 2D wide arrays: (row, col)-tiled kernel
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _plan_2d(shape, dtype_str, ksteps: int):
    """Choose the 2D kernel: ('thin', kchunk) — the row-banded kernel above
    (best when full rows are cheap) — or ('coltiled', R, C, kr, kc, kchunk)
    when the array is wide enough that full-width bands would starve the
    tile of rows (bf16 32768^2: 1.5x redundant compute in round 1)."""
    m, n = shape
    item = jnp.dtype(dtype_str).itemsize
    sub = _sublane(dtype_str)
    n_pad = _round_up(max(n, 128), 128)
    chip = _chip()

    def cost_thin(k):
        # additive model (cost_thin_2d): measured thin 4096^2 f32 =
        # 6.2e-12 s/pt-step; additive predicts 6.16e-12 where max() says
        # 5.63e-12
        return cost_thin_2d(n_pad, k, dtype_str, chip)

    k_thin = min(max(ksteps, 1), _thin_chunk_cap(n_pad, dtype_str))
    best_col = None
    for k in (4, 8, 16, 32):
        if k > max(ksteps, 1):
            continue
        kr = _round_up(k, sub)
        kc = 128
        for C in (2048, 4096, 8192):
            if C >= n_pad:  # col-tiling a narrow array is pure overhead
                continue
            for R in (128, 256, 512, 1024):
                R = _round_up(R, kr)
                R = min(R, _round_up(max(m, kr), kr))
                band = (R + 2 * kr) * (C + 2 * kc)
                tile = R * C
                if not _fits_vmem(band, tile, item):
                    continue
                # compile sanity: bands past the cap send Mosaic compiles
                # from ~1 min to 5 min (512 rows) to >12 min (1024 rows);
                # the modeled gain past it is <4%
                if band * 4 > chip.coltiled_band_cap_bytes:
                    continue
                compute = 11.0 * band / tile / chip.vpu_ops_per_s
                bw = (band + tile) * item / (tile * k) / chip.hbm_bytes_per_s
                key = (compute + bw, band, -k)
                if best_col is None or key < best_col[0]:
                    best_col = (key, R, C, kr, kc, k)
    # the thin-band kernel is the measured-proven default; switch only for
    # a clear (>10%) modeled win
    if best_col is not None and best_col[0][0] < 0.9 * cost_thin(k_thin):
        _, R, C, kr, kc, k = best_col
        return ("coltiled", R, C, kr, kc, k)
    return ("thin", k_thin)


def _make_kernel_2d_coltiled(r: float, R: int, C: int, kr: int, kc: int,
                             ksteps: int):
    """(row, col)-tiled 2D body: the thin kernel's full-band wrap rotates +
    masked multiplicative update, on a two-axis tile. Every op is
    lane/sublane-aligned. (A shrinking-slices body — neighbor reads as
    addressing offsets — was measured to send Mosaic into multi-minute
    compiles at deep unrolls: sublane/lane-misaligned slice offsets force
    per-step relayouts. Wrap-rotate band-edge corruption travels one cell
    per mini-step and stays inside the kr/kc margins, the same invariant as
    the thin kernel's.)"""
    rows = R + 2 * kr
    cols = C + 2 * kc

    def kernel(bounds_ref, *refs):
        i = pl.program_id(0)
        j = pl.program_id(1)
        out_ref = refs[-1]
        store_dt = out_ref.dtype
        acc_dt = accum_dtype_for(store_dt)
        band = _assemble_band(refs[:9], acc_dt)

        bshape = (rows, cols)
        grow = i * R - kr + jax.lax.broadcasted_iota(jnp.int32, bshape, 0)
        gcol = j * C - kc + jax.lax.broadcasted_iota(jnp.int32, bshape, 1)
        frozen = (
            (grow <= bounds_ref[0, 0]) | (grow >= bounds_ref[0, 1])
            | (gcol <= bounds_ref[0, 2]) | (gcol >= bounds_ref[0, 3])
        )
        maskr = jnp.where(frozen, 0.0, r).astype(acc_dt)

        for _ in range(ksteps):  # static unroll
            up = pltpu.roll(band, 1, 0)
            dn = pltpu.roll(band, rows - 1, 0)
            lf = pltpu.roll(band, 1, 1)
            rt = pltpu.roll(band, cols - 1, 1)
            # solo band is NaN-free by construction (reference form)
            # heat-tpu: allow[mosaic-kernel-safety] solo NaN-free freeze
            band = band + maskr * (up + dn + lf + rt - 4.0 * band)
        out_ref[:] = band[kr: kr + R, kc: kc + C].astype(store_dt)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("r", "ksteps", "R", "C", "kr", "kc",
                                    "logical_shape"))
def _pallas_2d_coltiled(Tp: jax.Array, r: float, ksteps: int, R: int, C: int,
                        kr: int, kc: int, logical_shape,
                        bounds: jax.Array | None = None) -> jax.Array:
    m_pad, n_pad = Tp.shape
    m, n = logical_shape
    assert m_pad % R == 0 and n_pad % C == 0
    assert R % kr == 0 and C % kc == 0 and ksteps <= min(kr, kc)
    if bounds is None:
        bounds = jnp.asarray([[0, m - 1, 0, n - 1]], jnp.int32)
    bounds = bounds.reshape(1, 4).astype(jnp.int32)
    grid = (m_pad // R, n_pad // C)
    smem = pl.BlockSpec((1, 4), lambda i, j: (0, 0), memory_space=pltpu.SMEM)
    in_specs, out_spec = _grid_specs_3x3(
        (R, C), (kr, kc), (m_pad // kr, n_pad // kc), ())
    band = (R + 2 * kr) * (C + 2 * kc)
    return pl.pallas_call(
        _make_kernel_2d_coltiled(float(r), R, C, kr, kc, ksteps),
        out_shape=jax.ShapeDtypeStruct(Tp.shape, Tp.dtype),
        grid=grid,
        name="heat_ftcs_2d_coltiled",
        in_specs=[smem] + in_specs,
        out_specs=out_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_chip().vmem_limit_bytes,
        ),
        cost_estimate=pl.CostEstimate(
            flops=11 * band * grid[0] * grid[1] * ksteps,
            bytes_accessed=(band + R * C) * grid[0] * grid[1]
            * Tp.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=_interpret(),
    )(bounds, *([Tp] * 9))


def plan_summary(shape, dtype_str: str, ksteps: int) -> str:
    """One-line human description of the kernel plan for ``shape`` — the
    geometry derived by the SAME rules the kernels use (keep this next to
    the planners; CLI/`plan` must not re-derive it)."""
    shape = tuple(shape)
    if not pallas_available(shape, jnp.dtype(dtype_str)):
        return ("XLA fused stencil (no Pallas plan for this shape/dtype — "
                "f64 or oversized lane extent)")
    if len(shape) == 2:
        p = _plan_2d(shape, dtype_str, ksteps)
        if p[0] == "thin":
            k = p[1]
            kpad = _halo_2d(k, dtype_str)
            n_pad = _round_up(max(shape[1], 128), 128)
            tile = _tile_2d(n_pad, kpad)
            return (f"thin-band 2D (rows banded, full-width); tile {tile} "
                    f"rows, halo {kpad}, per-pass chunk {k}, band "
                    f"{(tile + 2 * kpad) * n_pad * 4 / 2**20:.1f} MiB, "
                    f"halo-compute overhead {(tile + 2 * kpad) / tile:.2f}x")
        _, R, C, kr, kc, k = p
        band = (R + 2 * kr) * (C + 2 * kc)
        return (f"col-tiled 2D 3x3-halo; tile {R}x{C}, halo {kr}x{kc}, "
                f"per-pass chunk {k}, band {band * 4 / 2**20:.1f} MiB, "
                f"halo-compute "
                f"overhead {band / (R * C):.2f}x")
    (_, _, n_pad), R, M, k = _plan_3d(shape, dtype_str, min(ksteps, 8))
    km = _round_up(k, _sublane(dtype_str))
    band = (R + 2 * k) * (M + 2 * km)
    return (f"(row,mid)-tiled 3D 3x3-halo; tile {R}x{M}x{n_pad}, per-pass "
            f"chunk {k}, band {band * n_pad * 4 / 2**20:.1f} MiB, "
            f"halo-compute "
            f"overhead {band / (R * M):.2f}x")


# --------------------------------------------------------------------------
# public entry points (with transparent XLA fallback)
# --------------------------------------------------------------------------


def pallas_available(shape, dtype) -> bool:
    """Arbitrary 2D/3D shapes are supported via internal alignment padding;
    f64 (no TPU VPU support) falls back to XLA, as do 3D shapes whose lane
    extent is so large no tiled band fits VMEM (no plan exists)."""
    shape = tuple(shape)
    if jnp.dtype(dtype) == jnp.float64:
        return False
    if len(shape) == 3:
        return _plan_3d(shape, str(jnp.dtype(dtype)), 8) is not None
    return len(shape) == 2


def _multistep(T: jax.Array, r: float, ksteps: int,
               bounds: jax.Array | None = None) -> jax.Array:
    """Dispatch ksteps fused frozen-boundary steps, chunking fusion down to
    what each kernel's dependency-cone bound affords."""
    logical = tuple(T.shape)
    if T.ndim == 2:
        plan = _plan_2d(logical, str(T.dtype), ksteps)
        if plan[0] == "thin":
            n_pad = _round_up(max(logical[1], 128), 128)
            cap = _thin_chunk_cap(n_pad, str(T.dtype))
            for k in _pass_depths(ksteps, cap):
                T = _pallas_2d(T, r=float(r), ksteps=k, bounds=bounds)
            return T
        _, R, C, kr, kc, kchunk = plan
        aligned = (_round_up(max(logical[0], R), R),
                   _round_up(max(logical[1], C), C))
        if aligned != logical:
            T = jnp.pad(T, [(0, p - s) for p, s in zip(aligned, logical)])
        for k in _pass_depths(ksteps, kchunk):
            T = _pallas_2d_coltiled(T, r=float(r), ksteps=k, R=R, C=C,
                                    kr=kr, kc=kc, logical_shape=logical,
                                    bounds=bounds)
        if aligned != logical:
            T = T[: logical[0], : logical[1]]
        return T
    plan = _plan_3d(logical, str(T.dtype), ksteps)
    assert plan is not None, (
        f"no 3D kernel plan for {logical} {T.dtype} (gate on "
        f"pallas_available before calling)")
    aligned, _, _, kchunk = plan
    if aligned != logical:
        T = jnp.pad(T, [(0, p - s) for p, s in zip(aligned, logical)])
    for k in _pass_depths(ksteps, kchunk):
        T = _pallas_3d_aligned(T, r=float(r), ksteps=k, kplan=kchunk,
                               logical_shape=logical, bounds=bounds)
    if aligned != logical:
        T = T[: logical[0], : logical[1], : logical[2]]
    return T


def multistep_points(shape, dtype_str: str, ksteps: int) -> int:
    """Point-steps ``_multistep`` computes for ``ksteps`` steps on an array
    of ``shape``, from the plan it runs: each pass's band (halo rows or
    blocks, alignment padding) times the mini-steps computed on it. The
    useful work is ``prod(shape) * ksteps``; the rest is halo recompute
    and padding."""
    shape = tuple(shape)
    if len(shape) == 2:
        m, n = shape
        plan = _plan_2d(shape, dtype_str, ksteps)
        if plan[0] == "thin":
            n_pad = _round_up(max(n, 128), 128)
            total = 0
            for k in _pass_depths(ksteps, _thin_chunk_cap(n_pad, dtype_str)):
                n_pad, kpad, tile, m_pad = _thin_geometry(m, n, k, dtype_str)
                total += (m_pad // tile) * (tile + 2 * kpad) * n_pad * k
            return total
        _, R, C, kr, kc, _ = plan
        tiles = (_round_up(max(m, R), R) // R) * (_round_up(max(n, C), C) // C)
        return tiles * (R + 2 * kr) * (C + 2 * kc) * ksteps
    (m_pad, mid_pad, n_pad), R, M, kchunk = _plan_3d(shape, dtype_str, ksteps)
    km = _round_up(kchunk, _sublane(dtype_str))
    total = 0
    for k in _pass_depths(ksteps, kchunk):
        # the 3D body's mini-step s updates the band shrunk by s+1 a side
        total += sum((R + 2 * kchunk - 2 * (s + 1)) * (M + 2 * km - 2 * (s + 1))
                     for s in range(k))
    return (m_pad // R) * (mid_pad // M) * n_pad * total


def thin_carry_shape(shape, dtype_str: str, ksteps: int):
    """The aligned ``(m_pad, n_pad)`` array the thin-band kernel runs every
    pass of ``ksteps`` fused steps on, for a logical 2D ``shape``; None
    where the planner picks another kernel, the passes differ in geometry,
    or the plan at the aligned shape is not that same thin plan. A caller
    that keeps its state in this shape, with the logical array at its
    origin, and freezes every cell beyond it, runs ``_multistep`` on the
    state as it is: no pad or slice around the kernel."""
    shape = tuple(shape)
    if len(shape) != 2 or not pallas_available(shape, dtype_str):
        return None
    plan = _plan_2d(shape, dtype_str, ksteps)
    if plan[0] != "thin":
        return None
    m, n = shape
    cap = _thin_chunk_cap(_round_up(max(n, 128), 128), dtype_str)
    geoms = {_thin_geometry(m, n, k, dtype_str)
             for k in _pass_depths(ksteps, cap)}
    if len(geoms) != 1:
        return None
    n_pad, _, _, m_pad = geoms.pop()
    # the planner sees the aligned array, not the logical one: it must
    # pick the same thin plan there, which then pads nothing
    if _plan_2d((m_pad, n_pad), dtype_str, ksteps) != plan:
        return None
    return (m_pad, n_pad)


def ftcs_multistep_bounded_pallas(T: jax.Array, r: float, ksteps: int,
                                  bounds: jax.Array) -> jax.Array:
    """``ksteps`` fused FTCS steps freezing cells at or beyond ``bounds``
    (i32 [lo, hi] pair per dimension, flattened; may be traced values —
    e.g. computed from ``lax.axis_index`` inside shard_map).

    Contract: cells NOT frozen by ``bounds`` include array-edge cells whose
    out-of-range neighbors are garbage (wrap rotates / clamped halo blocks),
    so the caller MUST own a discard margin >= ksteps on every non-frozen
    side — exactly the halo-width invariant of the sharded backend's
    communication-avoiding exchange (one width-k exchange buys k steps).
    """
    assert pallas_available(T.shape, T.dtype), (T.shape, T.dtype)
    return _multistep(T, r, ksteps, bounds=jnp.asarray(bounds, jnp.int32))


def ftcs_step_edges_pallas(T: jax.Array, r: float) -> jax.Array:
    """One frozen-boundary FTCS step via the Pallas kernel, with transparent
    XLA fallback for dtypes the kernel doesn't cover."""
    if not pallas_available(T.shape, T.dtype):
        return ftcs_step_edges(T, r)
    return _multistep(T, r, 1)


def ftcs_step_ghost_pallas(T: jax.Array, r: float, bc_value) -> jax.Array:
    """Ghost-BC step via Pallas: pad with the bc ring, run the edges kernel
    on the padded array (its frozen ring IS the ghost ring), crop."""
    return ftcs_multistep_ghost_pallas(T, r, bc_value, 1)


def ftcs_multistep_edges_pallas(T: jax.Array, r: float, ksteps: int) -> jax.Array:
    """``ksteps`` frozen-boundary FTCS steps in fused kernel passes, with
    sequential XLA fallback where the kernel doesn't apply."""
    if pallas_available(T.shape, T.dtype):
        return _multistep(T, r, ksteps)
    out = T
    for _ in range(ksteps):
        out = ftcs_step_edges(out, r)
    return out


# periodic ("pbc") runs freeze nothing: bounds no cell index can satisfy
_NO_FREEZE = 2**30


def periodic_pad_width(shape, ksteps: int) -> int:
    """Wrap-ring width per chunk of the periodic multistep — the single
    derivation both the kernel dispatch and `plan` report (CLI must not
    re-derive planner geometry)."""
    cap = _KMAX_2D if len(shape) == 2 else 16  # 3D chunks further internally
    # keep the wrap ring within one period (jnp.pad wrap width <= extent)
    return max(1, min(cap, max(ksteps, 1), min(shape)))


def _periodic_passes(shape, dtype, ksteps: int):
    """Wrap-ring width (= depth) of each pass of the periodic multistep, or
    None when a wrap-padded shape has no kernel plan and XLA steps instead.
    Gates on EVERY wrap-padded shape the loop builds: the full chunks and
    the remainder pad differently, and for 3D a plan for the cap-padded
    shape does not guarantee one for the smaller remainder shape
    (_multistep asserts rather than falls back)."""
    depths = _pass_depths(ksteps, periodic_pad_width(shape, ksteps))
    if not all(pallas_available(tuple(s + 2 * w for s in shape), dtype)
               for w in set(depths)):
        return None
    return depths


def ftcs_multistep_periodic_pallas(T: jax.Array, r: float, ksteps: int) -> jax.Array:
    """``ksteps`` FTCS steps on the torus via the bounded kernel.

    Scheme: wrap-pad a width-k ghost ring (``jnp.pad mode="wrap"`` — the
    periodic analog of the halo exchange, one "message" from the opposite
    edge), run k fused steps with bounds that freeze nothing, crop. The
    wrap ring IS the discard margin the bounded kernel's contract demands,
    and ghost layer L is valid for the first k-L mini-steps — the same
    communication-avoiding invariant as the sharded backend's width-k
    exchange. Chunked so pad/crop overhead stays ~2 passes per _KMAX_2D
    steps.
    """
    if ksteps <= 0:
        return T
    nd = T.ndim
    depths = _periodic_passes(T.shape, T.dtype, ksteps)
    if depths is None:
        out = T
        for _ in range(ksteps):
            out = ftcs_step_periodic(out, r)
        return out
    bounds = jnp.asarray([[-_NO_FREEZE, _NO_FREEZE] * nd], jnp.int32)
    for k in depths:
        padded = jnp.pad(T, k, mode="wrap")
        out = _multistep(padded, r, k, bounds=bounds)
        ctr = tuple(slice(k, -k) for _ in range(nd))
        T = out[ctr]
    return T


def ftcs_step_periodic_pallas(T: jax.Array, r: float) -> jax.Array:
    """One periodic FTCS step via the Pallas kernel (XLA roll fallback)."""
    return ftcs_multistep_periodic_pallas(T, r, 1)


def ftcs_multistep_ghost_pallas(T: jax.Array, r: float, bc_value, ksteps: int) -> jax.Array:
    """``ksteps`` ghost-BC steps fused: the padded array's frozen outer ring
    IS the ghost ring, which never changes — so the edges multistep kernel on
    the padded array is exactly k ghost-BC steps."""
    if pallas_available(T.shape, T.dtype):
        padded = jnp.pad(T, 1, mode="constant",
                         constant_values=jnp.asarray(bc_value, T.dtype))
        out = _multistep(padded, r, ksteps)
        ctr = tuple(slice(1, -1) for _ in range(T.ndim))
        return out[ctr]
    out = T
    for _ in range(ksteps):
        out = ftcs_step_ghost(out, r, bc_value)
    return out


def multistep_points_bc(shape, dtype_str: str, bc: str,
                        ksteps: int) -> int | None:
    """Point-steps the kernels compute for ``ftcs_multistep_<bc>_pallas``
    of ``ksteps`` steps on a field of ``shape`` (``multistep_points`` over
    the array each BC form hands ``_multistep``), or None where that call
    steps with XLA."""
    shape = tuple(shape)
    if bc == "periodic":
        depths = _periodic_passes(shape, dtype_str, ksteps)
        if depths is None:
            return None
        return sum(multistep_points(tuple(s + 2 * k for s in shape),
                                    dtype_str, k) for k in depths)
    if not pallas_available(shape, dtype_str):
        return None
    if bc == "ghost":
        shape = tuple(s + 2 for s in shape)
    return multistep_points(shape, dtype_str, ksteps)


# --------------------------------------------------------------------------
# multi-lane serving kernels: the lane axis as a grid dimension
#
# The serving engine (serve/engine.py) steps up to L independent requests
# as one stacked (L, B+2, ...) array. Its reference chunk program is a
# masked *vmapped XLA* stencil; the kernels below are the Pallas port: the
# lane axis becomes grid dimension 0 over the existing 2D halo-slab / 3D
# 3x3-banded plans, and ONE kernel fuses (a) the per-lane interior mask
# (cells outside [lo, n-1-lo] of the per-lane request side n, SMEM-
# resident like bounds_ref), (b) the per-lane countdown gating (a lane
# whose remaining count ran out keeps its field, step-granular),
# (c) the per-lane isfinite health reduction, and (d) the per-lane
# numerics stats (ISSUE 15: final-mini-step residual, request-region
# min/max, total heat — SMEM-accumulated next to the finite bit) — so
# lane health AND solution-quality telemetry cost zero extra passes
# over the stack instead of separate post-chunk sweeps.
#
# Bit-identity with the XLA lane program is a hard contract (the XLA path
# stays the serving oracle): every mini-step replicates the exact
# arithmetic of serve/engine._lane_step — laplacian summed in
# ops.stencil.laplacian_interior's left-to-right order, update applied by
# SELECT (jnp.where), not the solo kernels' multiply-mask (0 * NaN would
# leak a blowing-up lane's NaN into its frozen ring where the oracle
# keeps old values), and the result rounded to the storage dtype EVERY
# mini-step (the fori_loop rounds per step; the solo kernels' round-once
# bf16 mode would diverge). Per-lane frozen bounds in buffer coords:
# cells <= lo or >= n+1-lo freeze, lo = 0 (ghost) or 1 (edges) — the
# margin ring, the unused bucket corner, and the kernel's alignment
# padding all land outside, so garbage there is never read by live cells
# (reads reach one cell per mini-step; live cells sit >= 1 cell inside).
# --------------------------------------------------------------------------


# fixed per-pass fusion depth of the 2D lane kernel: matches the serve
# default --chunk 16 (one pass per chunk), stays within every dtype's
# _halo_2d alignment, and — unlike the solo planner's shape-dependent
# chunk — keeps the padded STATE layout independent of the engine's
# chunk knob (tail programs reuse the steady layout with fewer steps).
_LANE_KP_2D = 16


@functools.lru_cache(maxsize=None)
def _plan_lane_2d(bucket_n: int, dtype_str: str):
    """Geometry for the multi-lane thin-band kernel over (L, m, m) lane
    slabs, m = bucket side + 2 margin: (m_pad, n_pad, tile, kpad, kp), or
    None when no row tile fits the band budget. The row tile is chosen to
    minimize the padded slab height (alignment rows are computed-then-
    frozen waste), tie-breaking toward fewer, larger tiles."""
    m = bucket_n + 2
    n_pad = _round_up(max(m, 128), 128)
    kp = _LANE_KP_2D
    kpad = _halo_2d(kp, dtype_str)
    budget = _chip().band_budget_bytes
    best = None
    t = kpad
    tmax = max(_round_up(m, kpad), kpad)
    while t <= tmax:
        if (t + 2 * kpad) * n_pad * 4 <= budget:
            m_pad = _round_up(max(m, t), t)
            cand = (m_pad, -t)
            if best is None or cand < best[0]:
                best = (cand, t, m_pad)
        t += kpad
    if best is None:
        return None
    _, tile, m_pad = best
    return m_pad, n_pad, tile, kpad, kp


@functools.lru_cache(maxsize=None)
def _plan_lane_3d(bucket_n: int, dtype_str: str):
    """3D lane geometry: the solo (row, mid)-tiled 3x3 plan for one lane
    slab — ((m_pad, mid_pad, n_pad), R, M, kchunk, km), or None when no
    band fits VMEM (the caller falls back to the XLA lane program)."""
    m = bucket_n + 2
    p = _plan_3d((m, m, m), dtype_str, _KMAX_3D)
    if p is None:
        return None
    (m_pad, mid_pad, n_pad), R, M, k = p
    return (m_pad, mid_pad, n_pad), R, M, k, _round_up(k, _sublane(dtype_str))


def lane_state_shape(ndim: int, bucket_n: int, dtype_str: str):
    """Per-lane padded slab shape the lane kernels step in place, or None
    when this (ndim, bucket, dtype) has no kernel plan (f64 — no TPU VPU
    f64 — or a 3D lane extent no band fits VMEM for). The serving engine
    keeps its stacked state in THIS layout for the whole engine lifetime
    (requests load into the [0 : B+2] corner; alignment padding is frozen
    by the per-lane bounds and never read by a live cell), so chunk
    dispatch pays zero per-call pad/crop."""
    if jnp.dtype(dtype_str) == jnp.float64:
        return None
    if ndim == 2:
        p = _plan_lane_2d(bucket_n, dtype_str)
        return None if p is None else (p[0], p[1])
    if ndim == 3:
        p = _plan_lane_3d(bucket_n, dtype_str)
        return None if p is None else p[0]
    return None


def lane_kernel_available(ndim: int, bucket_n: int, dtype_str: str) -> bool:
    """Can the Pallas lane kernels serve this bucket? (The serve knob's
    ``auto`` gate; explicit ``pallas`` on an unavailable bucket is a
    structured fallback, never an error — serve/engine.py.)"""
    return lane_state_shape(ndim, bucket_n, dtype_str) is not None


def _lane_finite_accumulate(fin_ref, lane, first_any, out_tile,
                            lanes: int):
    """Fuse the per-lane health verdict into the stencil pass: AND this
    program's output-tile isfinite verdict into its lane's slot of the
    ONE (1, L) SMEM bit vector every grid instance revisits (block index
    constant, so the block stays resident for the whole grid; Mosaic
    requires SMEM output blocks to span the full array). The very first
    grid instance initializes all L bits; each instance then ANDs via a
    dynamic per-lane SMEM store. bf16 upcasts for the reduction
    (finiteness is preserved exactly). Spelled ``|x| < inf`` rather than
    ``jnp.isfinite`` — false for NaN (any compare with NaN is false) and
    for both infinities — because Mosaic has no ``is_finite`` lowering."""
    ok = (jnp.abs(out_tile.astype(jnp.float32))
          < jnp.float32(float("inf"))).all().astype(jnp.int32)

    @pl.when(first_any)
    def _():
        for idx in range(lanes):  # static unroll: L scalar SMEM stores
            fin_ref[0, idx] = jnp.int32(1)

    fin_ref[0, lane] = jnp.minimum(fin_ref[0, lane], ok)


def _lane_stats_accumulate(stats_ref, lane, first_any, prev_tile,
                           out_tile, region, lanes: int):
    """Fuse the per-lane numerics stats (ISSUE 15) into the stencil pass,
    exactly the shape of ``_lane_finite_accumulate``: each grid instance
    reduces its output tile (float32, the bf16 accumulation discipline)
    under the REQUEST-REGION mask — buffer coords in ``[1, n_lane]``,
    the field including its Dirichlet ring, a different mask from the
    update's ``live`` — and merges four scalars into its lane's column
    of the ONE (4, L) float32 SMEM block: row 0 max|out - prev| over
    the pass's final mini-step (max-merge), row 1 region min
    (min-merge), row 2 region max (max-merge), row 3 region sum
    (add-merge). The first grid instance initializes every slot to the
    merge identities. Cells outside the region contribute the
    identities via select, so alignment padding and the margin never
    leak into a lane's stats."""
    f32 = out_tile.astype(jnp.float32)
    delta = jnp.abs(f32 - prev_tile.astype(jnp.float32))
    inf = jnp.float32(float("inf"))
    resid = jnp.where(region, delta, jnp.float32(0)).max()
    tmin = jnp.where(region, f32, inf).min()
    tmax = jnp.where(region, f32, -inf).max()
    heat = jnp.where(region, f32, jnp.float32(0)).sum()

    @pl.when(first_any)
    def _():
        for idx in range(lanes):  # static unroll: 4L scalar SMEM stores
            stats_ref[0, idx] = jnp.float32(0)
            stats_ref[1, idx] = inf
            stats_ref[2, idx] = -inf
            stats_ref[3, idx] = jnp.float32(0)

    stats_ref[0, lane] = jnp.maximum(stats_ref[0, lane], resid)
    stats_ref[1, lane] = jnp.minimum(stats_ref[1, lane], tmin)
    stats_ref[2, lane] = jnp.maximum(stats_ref[2, lane], tmax)
    stats_ref[3, lane] = stats_ref[3, lane] + heat


def _make_lane_kernel_2d(bc_lo: int, tile: int, kpad: int, n_pad: int,
                         ksteps: int, offset: int, lanes: int):
    """Multi-lane thin-band body: one (lane, row-tile) program instance.
    ``offset`` is the pass's global step index within the chunk — the
    countdown gate compares against the chunk-start ``remaining``."""
    rows = tile + 2 * kpad

    def kernel(r_ref, n_ref, rem_ref, prev_ref, cur_ref, next_ref,
               out_ref, fin_ref, stats_ref):
        lane = pl.program_id(0)
        i = pl.program_id(1)
        store_dt = out_ref.dtype
        acc_dt = accum_dtype_for(store_dt)
        # the band WORKS in the accumulation dtype but holds exactly
        # storage-rounded values: each update is rounded through the
        # storage dtype (the oracle's per-step rounding) and selected in
        # 32 bits (Mosaic has no sub-32-bit select); the final downcast
        # is then exact, so bf16 results stay byte-identical to XLA
        band = jnp.concatenate(
            [prev_ref[:], cur_ref[:], next_ref[:]], axis=1)[0].astype(acc_dt)
        n_l = n_ref[0, lane]
        rem_l = rem_ref[0, lane]
        r_l = r_ref[0, lane].astype(acc_dt)
        grow = i * tile - kpad + jax.lax.broadcasted_iota(
            jnp.int32, (rows, n_pad), 0)
        gcol = jax.lax.broadcasted_iota(jnp.int32, (rows, n_pad), 1)
        hi = n_l + 1 - bc_lo
        live = ((grow > bc_lo) & (grow < hi)
                & (gcol > bc_lo) & (gcol < hi))
        prevb = band
        for s in range(ksteps):  # static unroll
            if s == ksteps - 1:
                # pre-final-step band: the residual stat's reference.
                # Its out-tile rows are wrap-corruption-free too —
                # corruption travels one cell per mini-step and
                # ksteps - 1 < kpad (same invariant as `out`).
                prevb = band
            # XLA-lane-program order: +1 neighbors in axis order, then -1
            # neighbors, then the center term (laplacian_interior)
            p0 = pltpu.roll(band, rows - 1, 0)
            p1 = pltpu.roll(band, n_pad - 1, 1)
            m0 = pltpu.roll(band, 1, 0)
            m1 = pltpu.roll(band, 1, 1)
            lap = p0 + p1 + m0 + m1 + (-4.0) * band
            upd = (band + r_l * lap).astype(store_dt).astype(acc_dt)
            keep = jnp.logical_and(live, offset + s < rem_l)
            band = jnp.where(keep, upd, band)
        out = band[kpad: kpad + tile].astype(store_dt)
        out_ref[:] = out.reshape(1, tile, n_pad)
        first_any = jnp.logical_and(lane == 0, i == 0)
        _lane_finite_accumulate(fin_ref, lane, first_any, out, lanes)
        # request-region mask in OUT-TILE coords ([1, n_l] per axis —
        # the Dirichlet ring included; distinct from `live`)
        oshape = (tile, n_pad)
        orow = i * tile + jax.lax.broadcasted_iota(jnp.int32, oshape, 0)
        ocol = jax.lax.broadcasted_iota(jnp.int32, oshape, 1)
        region = ((orow >= 1) & (orow <= n_l)
                  & (ocol >= 1) & (ocol <= n_l))
        _lane_stats_accumulate(stats_ref, lane, first_any,
                               prevb[kpad: kpad + tile], out, region,
                               lanes)

    return kernel


def _lane_pallas_2d(fields: jax.Array, r, n, rem, bc_lo: int, ksteps: int,
                    offset: int, plan):
    """One fused pass of <= kpad mini-steps over every lane (grid =
    (L, row-tiles)). Traced inside the serving engine's jitted advance —
    no jit of its own."""
    m_pad, n_pad, tile, kpad, _ = plan
    L = fields.shape[0]
    assert fields.shape == (L, m_pad, n_pad), (fields.shape, plan)
    assert 1 <= ksteps <= kpad and tile % kpad == 0
    grid = (L, m_pad // tile)
    ratio = tile // kpad
    nhblk = m_pad // kpad
    smem = pl.BlockSpec((1, L), lambda l, i: (0, 0),
                        memory_space=pltpu.SMEM)
    halo = lambda imap: pl.BlockSpec((1, kpad, n_pad), imap,
                                     memory_space=pltpu.VMEM)
    main = lambda imap: pl.BlockSpec((1, tile, n_pad), imap,
                                     memory_space=pltpu.VMEM)
    band = tile + 2 * kpad
    out, fin, stats = pl.pallas_call(
        _make_lane_kernel_2d(bc_lo, tile, kpad, n_pad, ksteps, offset, L),
        out_shape=(jax.ShapeDtypeStruct(fields.shape, fields.dtype),
                   jax.ShapeDtypeStruct((1, L), jnp.int32),
                   jax.ShapeDtypeStruct((4, L), jnp.float32)),
        grid=grid,
        name="heat_lane_2d",
        in_specs=[
            smem, smem, smem,
            halo(lambda l, i: (l, jnp.maximum(i * ratio - 1, 0), 0)),
            main(lambda l, i: (l, i, 0)),
            halo(lambda l, i: (l, jnp.minimum((i + 1) * ratio, nhblk - 1),
                               0)),
        ],
        out_specs=(main(lambda l, i: (l, i, 0)),
                   pl.BlockSpec((1, L), lambda l, i: (0, 0),
                                memory_space=pltpu.SMEM),
                   pl.BlockSpec((4, L), lambda l, i: (0, 0),
                                memory_space=pltpu.SMEM)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_chip().vmem_limit_bytes,
        ),
        cost_estimate=pl.CostEstimate(
            flops=11 * band * n_pad * L * grid[1] * ksteps,
            bytes_accessed=(2 * m_pad + 2 * kpad * grid[1]) * n_pad * L
            * fields.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=_interpret(),
    )(jnp.asarray(r).reshape(1, L),
      jnp.asarray(n, jnp.int32).reshape(1, L),
      jnp.asarray(rem, jnp.int32).reshape(1, L),
      fields, fields, fields)
    return out, fin[0], stats


def _lane_grid_specs_3x3(R: int, M: int, ki: int, kj: int, nblocks,
                         n_pad: int):
    """The 3x3 halo-neighborhood BlockSpecs with a leading LANE block dim:
    maps take (l, i, j) and clamp within lane l's own slab."""
    ri, rj = R // ki, M // kj
    ni, nj = nblocks

    def icl(i):
        return jnp.clip(i, 0, ni - 1)

    def jcl(j):
        return jnp.clip(j, 0, nj - 1)

    def bs(bi, bj, imap):
        return pl.BlockSpec((1, bi, bj, n_pad), imap,
                            memory_space=pltpu.VMEM)

    specs = [
        bs(ki, kj, lambda l, i, j: (l, icl(i * ri - 1), jcl(j * rj - 1), 0)),
        bs(ki, M, lambda l, i, j: (l, icl(i * ri - 1), j, 0)),
        bs(ki, kj, lambda l, i, j: (l, icl(i * ri - 1), jcl((j + 1) * rj), 0)),
        bs(R, kj, lambda l, i, j: (l, i, jcl(j * rj - 1), 0)),
        bs(R, M, lambda l, i, j: (l, i, j, 0)),
        bs(R, kj, lambda l, i, j: (l, i, jcl((j + 1) * rj), 0)),
        bs(ki, kj, lambda l, i, j: (l, icl((i + 1) * ri), jcl(j * rj - 1), 0)),
        bs(ki, M, lambda l, i, j: (l, icl((i + 1) * ri), j, 0)),
        bs(ki, kj, lambda l, i, j: (l, icl((i + 1) * ri), jcl((j + 1) * rj), 0)),
    ]
    return specs, bs(R, M, lambda l, i, j: (l, i, j, 0))


def _make_lane_kernel_3d(bc_lo: int, R: int, M: int, kp: int, km: int,
                         n_pad: int, ksteps: int, offset: int,
                         lanes: int):
    """Multi-lane (row, mid)-tiled 3D body. Unlike the solo 3D kernel's
    shrinking slices, every mini-step runs full-band wrap rotates on ALL
    three axes with a select-kept update — the col-tiled 2D kernel's
    proven-on-Mosaic shape discipline (shrinking 3D slices hand Mosaic
    sublane-misaligned rotate shapes, rejected outright by current
    compilers). Band-edge wrap corruption travels one cell per mini-step
    and ksteps <= kp <= km, so it never reaches the out tile — the same
    invariant as every other kernel in this file. Select-kept, per-lane
    bounded/gated, storage-rounded each step (the oracle contract)."""
    rows = R + 2 * kp
    mids = M + 2 * km

    def kernel(r_ref, n_ref, rem_ref, *refs):
        out_ref, fin_ref, stats_ref = refs[-3], refs[-2], refs[-1]
        lane = pl.program_id(0)
        i = pl.program_id(1)
        j = pl.program_id(2)
        store_dt = out_ref.dtype
        acc_dt = accum_dtype_for(store_dt)
        rows_g = [jnp.concatenate([refs[3 * g][:], refs[3 * g + 1][:],
                                   refs[3 * g + 2][:]], axis=2)
                  for g in range(3)]
        # band works in the accumulation dtype, holding exactly storage-
        # rounded values (see the 2D kernel: 32-bit select + exact final
        # downcast keep bf16 byte-identical to the oracle)
        band = jnp.concatenate(rows_g, axis=1)[0].astype(acc_dt)
        n_l = n_ref[0, lane]
        rem_l = rem_ref[0, lane]
        r_l = r_ref[0, lane].astype(acc_dt)
        hi = n_l + 1 - bc_lo
        bshape = (rows, mids, n_pad)
        grow = i * R - kp + jax.lax.broadcasted_iota(jnp.int32, bshape, 0)
        gmid = j * M - km + jax.lax.broadcasted_iota(jnp.int32, bshape, 1)
        gcol = jax.lax.broadcasted_iota(jnp.int32, bshape, 2)
        live = ((grow > bc_lo) & (grow < hi) & (gmid > bc_lo) & (gmid < hi)
                & (gcol > bc_lo) & (gcol < hi))
        prevb = band
        for s in range(ksteps):  # static unroll, constant shapes
            if s == ksteps - 1:
                # pre-final-step band for the residual stat (wrap-safe
                # on the out tile: ksteps - 1 < kp <= km)
                prevb = band
            # XLA-lane-program order: +axis0 +axis1 +axis2, then -axis0
            # -axis1 -axis2, then the center term (laplacian_interior)
            p0 = pltpu.roll(band, rows - 1, 0)
            p1 = pltpu.roll(band, mids - 1, 1)
            p2 = pltpu.roll(band, n_pad - 1, 2)
            m0 = pltpu.roll(band, 1, 0)
            m1 = pltpu.roll(band, 1, 1)
            m2 = pltpu.roll(band, 1, 2)
            lap = p0 + p1 + p2 + m0 + m1 + m2 + (-6.0) * band
            upd = (band + r_l * lap).astype(store_dt).astype(acc_dt)
            keep = jnp.logical_and(live, offset + s < rem_l)
            band = jnp.where(keep, upd, band)
        out = jax.lax.slice(
            band, (kp, km, 0), (kp + R, km + M, n_pad)).astype(store_dt)
        out_ref[:] = out.reshape(1, R, M, n_pad)
        first_any = jnp.logical_and(lane == 0,
                                    jnp.logical_and(i == 0, j == 0))
        _lane_finite_accumulate(fin_ref, lane, first_any, out, lanes)
        # request-region mask in OUT-TILE coords (Dirichlet ring in,
        # padding/margin out — distinct from `live`)
        oshape = (R, M, n_pad)
        orow = i * R + jax.lax.broadcasted_iota(jnp.int32, oshape, 0)
        omid = j * M + jax.lax.broadcasted_iota(jnp.int32, oshape, 1)
        ocol = jax.lax.broadcasted_iota(jnp.int32, oshape, 2)
        region = ((orow >= 1) & (orow <= n_l) & (omid >= 1) & (omid <= n_l)
                  & (ocol >= 1) & (ocol <= n_l))
        prev_out = jax.lax.slice(prevb, (kp, km, 0),
                                 (kp + R, km + M, n_pad))
        _lane_stats_accumulate(stats_ref, lane, first_any, prev_out, out,
                               region, lanes)

    return kernel


def _lane_pallas_3d(fields: jax.Array, r, n, rem, bc_lo: int, ksteps: int,
                    offset: int, plan):
    """One fused pass of <= kchunk mini-steps over every lane (grid =
    (L, row-tiles, mid-tiles))."""
    (m_pad, mid_pad, n_pad), R, M, kp, km = plan
    L = fields.shape[0]
    assert fields.shape == (L, m_pad, mid_pad, n_pad), (fields.shape, plan)
    assert 1 <= ksteps <= kp
    grid = (L, m_pad // R, mid_pad // M)
    smem = pl.BlockSpec((1, L), lambda l, i, j: (0, 0),
                        memory_space=pltpu.SMEM)
    in_specs, out_spec = _lane_grid_specs_3x3(
        R, M, kp, km, (m_pad // kp, mid_pad // km), n_pad)
    band = (R + 2 * kp) * (M + 2 * km)
    out, fin, stats = pl.pallas_call(
        _make_lane_kernel_3d(bc_lo, R, M, kp, km, n_pad, ksteps, offset,
                             L),
        out_shape=(jax.ShapeDtypeStruct(fields.shape, fields.dtype),
                   jax.ShapeDtypeStruct((1, L), jnp.int32),
                   jax.ShapeDtypeStruct((4, L), jnp.float32)),
        grid=grid,
        name="heat_lane_3d",
        in_specs=[smem, smem, smem] + in_specs,
        out_specs=(out_spec,
                   pl.BlockSpec((1, L), lambda l, i, j: (0, 0),
                                memory_space=pltpu.SMEM),
                   pl.BlockSpec((4, L), lambda l, i, j: (0, 0),
                                memory_space=pltpu.SMEM)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_chip().vmem_limit_bytes,
        ),
        cost_estimate=pl.CostEstimate(
            flops=13 * band * n_pad * L * grid[1] * grid[2] * ksteps,
            bytes_accessed=(band + R * M) * n_pad * L * grid[1] * grid[2]
            * fields.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=_interpret(),
    )(jnp.asarray(r).reshape(1, L),
      jnp.asarray(n, jnp.int32).reshape(1, L),
      jnp.asarray(rem, jnp.int32).reshape(1, L),
      *([fields] * 9))
    return out, fin[0], stats


def lane_multistep(fields: jax.Array, r, n, rem, ksteps: int, bc_lo: int,
                   bucket_n: int):
    """``ksteps`` masked, countdown-gated FTCS steps over a stacked lane
    array via the multi-lane Pallas kernels, health reduction and
    numerics stats fused in.

    ``fields`` is (L,) + ``lane_state_shape(...)`` (the engine keeps its
    stack in the padded layout); ``r``/``n``/``rem`` are the per-lane
    scalar vectors of the serving engine's chunk program. Returns
    ``(fields, finite, stats)`` — ``finite`` a per-lane bool, False iff
    that lane's post-chunk slab holds a non-finite value; ``stats`` a
    (4, L) float32 of per-lane (resid, tmin, tmax, heat) over the
    request region (serve/engine.BOUNDARY_ROWS rows 2-5). Multi-pass
    chunks AND the finite bits across passes and keep the LAST pass's
    stats — the pass holding the chunk's final mini-step, whose
    residual/min/max/heat are the chunk-boundary values by definition.
    Stats are tolerance-compatible, not bit-equal, with the XLA lane
    program's (grid-tiled reduction order differs); the field bytes and
    rows 0-1 stay bit-exact. Gate callers on ``lane_kernel_available``;
    chunks deeper than the per-pass fusion cap run as multiple passes
    with the countdown gate offset so a lane still stops at exactly its
    own step count."""
    assert ksteps >= 1, ksteps
    nd = fields.ndim - 1
    dtype_str = str(fields.dtype)
    if nd == 2:
        plan = _plan_lane_2d(bucket_n, dtype_str)
        step, kp = _lane_pallas_2d, plan[4]
    else:
        plan = _plan_lane_3d(bucket_n, dtype_str)
        step, kp = _lane_pallas_3d, plan[3]
    assert plan is not None, (
        f"no lane kernel plan for {nd}d bucket {bucket_n} {dtype_str} "
        f"(gate on lane_kernel_available before calling)")
    fin = stats = None
    done = 0
    while done < ksteps:
        kpass = min(kp, ksteps - done)
        fields, f, stats = step(fields, r, n, rem, bc_lo=bc_lo,
                                ksteps=kpass, offset=done, plan=plan)
        fin = f if fin is None else jnp.minimum(fin, f)
        done += kpass
    return fields, fin.astype(bool), stats


# the plan caches embed the chip's rates/caps in their values; a chip-model
# override (tests, what-if planning) must flush them
machine.register_cache(_plan_2d.cache_clear)
machine.register_cache(_plan_3d.cache_clear)
machine.register_cache(_plan_lane_2d.cache_clear)
machine.register_cache(_plan_lane_3d.cache_clear)
