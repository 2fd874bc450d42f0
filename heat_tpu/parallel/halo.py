"""Halo (ghost) exchange over the device mesh.

TPU-native replacement for the reference's swap machinery
(``fortran/mpi+cuda/heat.F90:143-195`` and the HIP pack/unpack kernels
``fortran/hip/heat_kernel.cpp:63-150``):

- pack kernels      -> array slices of the shard (XLA fuses the "pack")
- ``mpi_sendrecv``  -> paired ``lax.ppermute`` shifts over ICI/DCN
- ``mpi_proc_null`` -> ppermute's missing-edge zeros, overwritten with the
  Dirichlet ``bc_value`` at global domain edges (non-periodic, matching
  ``pbc=.false.``, fortran/mpi+cuda/heat.F90:76 and the unpack guards
  :174-191). ``periodic=True`` enables the topology the reference's
  communicator is built to carry but never switches on (the ``pbc``
  periods argument of ``mpi_cart_create``, :97): the ppermute ring closes
  (last shard exchanges with the first) and no ghost is pinned.
- the column send slabs of a 2-D shard whose array fits the TPU tiles
  are cut by a small Pallas copy (``lane_cut_plan``, ``cut_lane_slabs``)
  rather than by an XLA slice, which the compiler may serve from a
  column-major copy of the whole shard.
- CUDA-aware vs NO_AWARE staged duality (:162-172) -> ``staged=True`` routes
  every halo slab through a host round-trip (``jax.pure_callback``), the
  honest analog of the D2H / sendrecv-on-host / H2D path; the default sends
  device buffers directly over the interconnect.

All functions run *inside* ``shard_map``: they see the local shard and use
collective axis names.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..ops.pallas_stencil import _interpret, _sublane


def _stage_through_host(x: jax.Array) -> jax.Array:
    """Round-trip a slab through host memory (the NO_AWARE staged path,
    fortran/mpi+cuda/heat.F90:162-168: T1s = Td1s ... Td1r = T1r)."""
    return jax.pure_callback(
        lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype), x,
        vmap_method="sequential",
    )


# the name the exchange's collectives carry in the program's op metadata
SCOPE = "heat_halo_exchange"


def _pairs(size: int, periodic: bool, from_prev: bool) -> list:
    """(source, destination) shard pairs of one shift along an axis."""
    pairs = [(i, (i + 1) % size) for i in range(size)] if periodic else [
        (i, i + 1) for i in range(size - 1)]
    return pairs if from_prev else [(d, s) for s, d in pairs]


def _shift_from_prev(slab, axis_name: str, size: int, periodic: bool = False):
    """Each shard receives the slab of its left/previous neighbor."""
    with jax.named_scope(SCOPE):
        return lax.ppermute(slab, axis_name, _pairs(size, periodic, True))


def _shift_from_next(slab, axis_name: str, size: int, periodic: bool = False):
    with jax.named_scope(SCOPE):
        return lax.ppermute(slab, axis_name, _pairs(size, periodic, False))


def exchange_bytes(padded_shape: Sequence[int], axis_sizes: Sequence[int],
                   width: int, itemsize: int, periodic: bool = False) -> int:
    """Bytes one exchange of a ``width``-deep ghost ring sends from one
    device to another, averaged over the mesh's devices (to the nearest
    byte). For each axis every ppermute pair between two different shards
    carries one send slab: ``width`` deep on that axis and the full padded
    extent of the others, as ``halo_exchange_indep`` cuts it. An edge
    shard of a non-periodic axis has one such pair, an inner shard two; a
    periodic axis of one shard sends to itself, which moves nothing
    between devices."""
    total = 0.0
    for d, size in enumerate(axis_sizes):
        slab = int(np.prod(padded_shape)) // padded_shape[d] * width
        crossing = sum(s != t for prev in (True, False)
                       for s, t in _pairs(size, periodic, prev))
        # ``crossing`` sends in each of the mesh's lines along axis d
        total += crossing * slab * itemsize / size
    return int(round(total))


def _cut(padded: jax.Array, logical, d: int, sl_d: slice) -> tuple:
    """Index of a slab of ``padded`` that is ``sl_d`` on axis ``d`` and
    spans the ``logical`` extent of the others (the whole array where the
    two agree)."""
    return tuple(sl_d if e == d else
                 slice(None) if logical[e] == padded.shape[e] else
                 slice(0, logical[e]) for e in range(padded.ndim))


# the lane width of a TPU tile, and the most rows one grid step of the
# strip copy moves (a few hundred KiB a block)
_LANES = 128
_CUT_ROWS = 1024


def lane_cut_plan(shape, dtype, width: int, logical=None):
    """How ``cut_lane_slabs`` copies the column send slabs of a shard
    (axis 1, the lane axis), or None where the shard does not fit the TPU
    tiles and the slabs are cut by an XLA slice: ``(rows, lo, hi)``, the
    row block and, for the low and the high slab, ``(first, count)`` of
    the 128-lane blocks of the array that hold it. A shard fits where it
    is 2-D in float32 or bfloat16, its rows are a multiple of the sublane
    tile, and each slab lies inside one 128-lane block of the array or
    two neighbouring ones. ``logical`` as in ``halo_exchange``."""
    shape = tuple(shape)
    if len(shape) != 2 or jnp.dtype(dtype) not in (jnp.float32,
                                                   jnp.bfloat16):
        return None
    m, n = shape
    sub = _sublane(dtype)
    if m % sub:
        return None
    last = shape[1] if logical is None else logical[1]
    blocks = []
    for c0 in (width, last - 2 * width):
        b0, b1 = c0 // _LANES, (c0 + width - 1) // _LANES
        if b1 - b0 > 1 or (b1 + 1) * _LANES > n:
            return None
        blocks.append((b0, b1 - b0 + 1))
    rows = max(r for r in range(sub, min(m, _CUT_ROWS) + 1, sub)
               if m % r == 0)
    return (rows, *blocks)


def _make_cut_kernel(n_lo: int, n_hi: int):
    def kernel(*refs):
        blocks, lo_ref, hi_ref = refs[:-2], refs[-2], refs[-1]
        for j in range(n_lo):
            lo_ref[:, j * _LANES:(j + 1) * _LANES] = blocks[j][...]
        for j in range(n_hi):
            hi_ref[:, j * _LANES:(j + 1) * _LANES] = blocks[n_lo + j][...]

    return kernel


def cut_lane_slabs(padded: jax.Array, plan, width: int, logical) -> tuple:
    """The column send slabs ``(lo, hi)`` of ``padded`` on axis 1, as
    ``halo_exchange`` cuts them, sliced from the two strips that hold
    them: every row, by the 128-lane blocks ``plan`` names
    (``lane_cut_plan``), copied out by one Pallas kernel. XLA cuts a
    narrow column slab through a column-major layout, and where the shard
    is a loop's carried state layout assignment pushes that layout back
    onto the whole shard: a copy of all of it, every loop iteration. A
    custom call's operand keeps its row-major layout, so that copy stops
    at the strips."""
    rows, (lo0, n_lo), (hi0, n_hi) = plan
    m = padded.shape[0]

    def block(b):
        return pl.BlockSpec((rows, _LANES), lambda i: (i, b))

    def strip(count):
        return pl.BlockSpec((rows, count * _LANES), lambda i: (i, 0))

    cols = [*range(lo0, lo0 + n_lo), *range(hi0, hi0 + n_hi)]
    lo, hi = pl.pallas_call(
        _make_cut_kernel(n_lo, n_hi),
        out_shape=[jax.ShapeDtypeStruct((m, c * _LANES), padded.dtype)
                   for c in (n_lo, n_hi)],
        grid=(m // rows,),
        name="heat_halo_cut",
        in_specs=[block(b) for b in cols],
        out_specs=[strip(n_lo), strip(n_hi)],
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=2 * m * len(cols) * _LANES
            * padded.dtype.itemsize),
        interpret=_interpret(),
    )(*[padded] * len(cols))
    w, (rows_l, cols_l) = width, logical
    c_lo = w - lo0 * _LANES
    c_hi = cols_l - 2 * w - hi0 * _LANES
    return lo[:rows_l, c_lo:c_lo + w], hi[:rows_l, c_hi:c_hi + w]


def halo_exchange(
    padded: jax.Array,
    axis_names: Sequence[str],
    axis_sizes: Sequence[int],
    bc_value,
    staged: bool = False,
    width: int = 1,
    periodic: bool = False,
    logical: Sequence[int] | None = None,
) -> jax.Array:
    """Refresh a ``width``-cell ghost ring of a padded local shard.

    ``padded`` has shape ``(nx+2w, ny+2w[, nz+2w])``: owned cells in the
    interior, ghosts in the outer ring (the reference's
    ``(1-ng:nx+ng, 1-ng:ny+ng)`` allocation with ng=1,
    fortran/mpi+cuda/heat.F90:41,107; here ng is a parameter to support
    communication-avoiding fused steps). For each decomposed axis the owned
    edge slabs travel to the neighbors' ghost slots; at global domain edges
    ghosts hold ``bc_value`` (Dirichlet, :243-251).

    Axes are exchanged **sequentially with full-extent slabs**: the slab for
    axis d spans the entire padded extent of every other axis, so later-axis
    exchanges forward the ghosts just received — after all axes, corner
    ghost regions hold true diagonal-neighbor data (needed by fused
    multi-step updates; a single 5/7-point step never reads corners, so
    ng=1 behavior is unchanged).

    ``logical`` (default: the whole array) is the extent of the padded
    shard at ``padded``'s origin, where ``padded`` carries alignment cells
    beyond it: slabs are cut and written inside that extent.

    No solve path calls this form: the sharded backend exchanges with
    ``halo_exchange_indep``. It stays as the plain reference that
    tests/test_sharded.py::test_halo_exchange_indep_bitwise holds the
    indep form to, bit for bit.
    """
    w = width
    L = tuple(padded.shape if logical is None else logical)
    bc = jnp.asarray(bc_value, padded.dtype)
    out = padded
    for d, (name, size) in enumerate(zip(axis_names, axis_sizes)):
        idx = lax.axis_index(name)

        def slab(sl_d):
            return _cut(padded, L, d, sl_d)

        send_lo = out[slab(slice(w, 2 * w))]       # first owned planes -> prev
        send_hi = out[slab(slice(L[d] - 2 * w, L[d] - w))]  # last -> next
        if staged:
            send_lo = _stage_through_host(send_lo)
            send_hi = _stage_through_host(send_hi)
        from_prev = _shift_from_prev(send_hi, name, size, periodic)
        from_next = _shift_from_next(send_lo, name, size, periodic)
        if staged:
            from_prev = _stage_through_host(from_prev)
            from_next = _stage_through_host(from_next)
        if not periodic:
            # Global-edge shards got zeros (no ppermute pair, ==
            # mpi_proc_null): pin their ghosts to the boundary temperature.
            from_prev = jnp.where(idx == 0, bc, from_prev)
            from_next = jnp.where(idx == size - 1, bc, from_next)

        out = out.at[slab(slice(0, w))].set(from_prev)
        out = out.at[slab(slice(L[d] - w, L[d]))].set(from_next)
    return out


def halo_exchange_indep(
    padded: jax.Array,
    axis_names: Sequence[str],
    axis_sizes: Sequence[int],
    bc_value,
    staged: bool = False,
    width: int = 1,
    periodic: bool = False,
    logical: Sequence[int] | None = None,
) -> jax.Array:
    """``halo_exchange`` with all ghost writes made independent: the
    sharded backend's one exchange.

    The sequential formulation reads axis d's send slabs from the
    already-ghost-updated array (that is how corner ghosts forward), so
    each axis's update-slice depends on the previous axis's — XLA can be
    forced to materialize the intermediate (the round-3 exchange lab
    measured a full-padded-array copy per exchange in the compiled
    advance). Here every send slab is cut from the ORIGINAL padded array,
    with earlier-axis corner data stitched in from those axes' received
    slabs (slab-sized updates, not full-array), so axis d's ppermutes
    start only after those of axes e<d land. The 2*nd ghost writes then
    all read from ``padded`` only, in increasing axis order (later axes
    own the corners), so XLA is free to apply them as one in-place pass.
    Owned values and ghost values are bit-identical to ``halo_exchange``
    — pinned by tests/test_sharded.py::test_halo_exchange_indep_bitwise.
    ``logical`` as in ``halo_exchange``: the slabs span it, not the
    alignment cells beyond. Where the shard fits the TPU tiles
    (``lane_cut_plan``), the column slabs of axis 1 are cut from strips
    that a Pallas kernel copies out (``cut_lane_slabs``); the values are
    the same.
    """
    nd = padded.ndim
    w = width
    L = tuple(padded.shape if logical is None else logical)
    bc = jnp.asarray(bc_value, padded.dtype)

    def slab(d, sl_d):
        sl = [slice(None)] * nd
        sl[d] = sl_d
        return tuple(sl)

    # per axis: the received (from_prev, from_next) slabs, each spanning
    # the padded extent of the other axes, earlier axes' corners included
    plan = lane_cut_plan(padded.shape, padded.dtype, w, L)
    recvs = []
    for d, (name, size) in enumerate(zip(axis_names, axis_sizes)):
        idx = lax.axis_index(name)
        if d == 1 and plan is not None:
            send_lo, send_hi = cut_lane_slabs(padded, plan, w, L)
        else:
            send_lo = padded[_cut(padded, L, d, slice(w, 2 * w))]
            send_hi = padded[_cut(padded, L, d,
                                  slice(L[d] - 2 * w, L[d] - w))]
        # corner forwarding: overwrite the earlier-axis margins of the
        # send slab with those axes' fresh ghosts (what the sequential
        # scheme reads from the updated array)
        for e in range(d):
            ep, en = recvs[e]
            send_lo = send_lo.at[slab(e, slice(0, w))].set(
                ep[slab(d, slice(w, 2 * w))])
            send_lo = send_lo.at[slab(e, slice(-w, None))].set(
                en[slab(d, slice(w, 2 * w))])
            send_hi = send_hi.at[slab(e, slice(0, w))].set(
                ep[slab(d, slice(-2 * w, -w))])
            send_hi = send_hi.at[slab(e, slice(-w, None))].set(
                en[slab(d, slice(-2 * w, -w))])
        if staged:
            send_lo = _stage_through_host(send_lo)
            send_hi = _stage_through_host(send_hi)
        from_prev = _shift_from_prev(send_hi, name, size, periodic)
        from_next = _shift_from_next(send_lo, name, size, periodic)
        if staged:
            from_prev = _stage_through_host(from_prev)
            from_next = _stage_through_host(from_next)
        if not periodic:
            from_prev = jnp.where(idx == 0, bc, from_prev)
            from_next = jnp.where(idx == size - 1, bc, from_next)
        recvs.append((from_prev, from_next))

    out = padded
    for d, (from_prev, from_next) in enumerate(recvs):
        out = out.at[_cut(padded, L, d, slice(0, w))].set(from_prev)
        out = out.at[_cut(padded, L, d, slice(L[d] - w, L[d]))].set(from_next)
    return out


def halo_pad(local: jax.Array, bc_value, width: int = 1) -> jax.Array:
    """Allocate the ghost ring around an owned shard (ghosts = bc_value)."""
    return jnp.pad(local, width, mode="constant",
                   constant_values=jnp.asarray(bc_value, local.dtype))


