"""Distributed backend: shard_map + ppermute halo exchange over a device mesh.

The TPU-native rebuild of the reference's distributed flagship
(``fortran/mpi+cuda/heat.F90``) and its HIP twin: the global field is one
jax.Array sharded over a named mesh; each timestep every shard refreshes a
one-cell ghost ring from its neighbors (``parallel.halo``) and applies the
FTCS update to all owned cells. SPMD is JAX's native model — the "same
binary on every rank" structure of the reference comes for free.

Step ordering: the reference updates then swaps (update-then-swap,
fortran/mpi+cuda/heat.F90:206-219), relying on ICs pre-filling the ghosts for
the first step; we default to the causally-clean swap-then-update.
``parity_order=True`` runs the literal reference ordering instead
(``make_parity_machinery``): the padded field is the carried state, every
step updates owned cells against ghosts as-they-are, then swaps. IC starts
bit-match the default order (the IC fills ghosts with exactly what the
first exchange delivers); explicit-T0 starts expose the reference's
stale-ghost first step, where the orders genuinely diverge — see
tests/test_parity_order.py for the literal transcription oracle.

BC semantics:
- ``ghost`` (MPI parity): all owned cells update; global-edge ghosts pinned
  at ``bc_value`` (fortran/mpi+cuda/heat.F90:243-251).
- ``edges`` (serial parity): ditto, then cells on the global boundary ring
  are frozen back — the decomposed run matches the serial oracle bit-for-bit
  in f64.
- ``periodic``: the ppermute ring closes (last shard exchanges with first)
  and nothing is pinned — the ``pbc=.true.`` cartesian topology the
  reference's communicator is built for but never enables
  (fortran/mpi+cuda/heat.F90:76,97).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import HeatConfig
from ..ops.pallas_stencil import (_KMAX_2D, ftcs_multistep_bounded_pallas,
                                  multistep_points, pallas_available,
                                  thin_carry_shape)
from ..ops.stencil import accum_dtype_for, laplacian_interior
from ..parallel.halo import (exchange_bytes, halo_exchange_indep, halo_pad,
                             lane_cut_plan)
from ..parallel.mesh import build_mesh, validate_divisible
from ..runtime.logging import master_print
from ..utils import jnp_dtype
from . import SolveResult, register
from .common import drive, host_fetch, resolve_initial_field


def _local_kernel_is_pallas(cfg: HeatConfig) -> bool:
    """Whether each shard steps with the Pallas kernel (else XLA)."""
    kernel_ok = pallas_available((cfg.n,) * cfg.ndim, jnp_dtype(cfg.dtype))
    if cfg.local_kernel == "pallas" and not kernel_ok:
        raise ValueError(
            f"local_kernel='pallas' does not support dtype={cfg.dtype!r} "
            f"(no f64 on the TPU VPU); use local_kernel='xla' or 'auto'")
    return cfg.local_kernel == "pallas" or (
        cfg.local_kernel == "auto"
        and jax.default_backend() == "tpu"
        and kernel_ok
    )


def make_local_multistep(cfg: HeatConfig, axis_names, axis_sizes):
    """Build ``local_multi(local, w)``: one halo exchange of width w, then w
    fused FTCS steps — the communication-avoiding scheme (runs inside
    shard_map). w=1 is exactly the reference's every-step exchange
    (fortran/mpi+cuda/heat.F90:206-219); w>1 trades a k-deep halo (bigger
    message, same count/k) for k-fewer collectives, with owned-cell values
    bit-identical because ghost layer L is mathematically valid for the
    first w-L mini-steps — precisely when it is read.
    """
    r = cfg.r
    bc_value = cfg.bc_value
    staged = cfg.comm == "staged"
    periodic = cfg.bc == "periodic"
    n = cfg.n

    use_pallas = _local_kernel_is_pallas(cfg)

    def _shard_bounds(padded_shape, wpad: int) -> list:
        """Per-axis [lo, hi] freeze bounds in PADDED shard coordinates:
        only global-domain edges freeze (Dirichlet ghosts, plus the
        boundary ring under "edges" semantics), and every cell at or
        beyond the logical padded extent (alignment cells, which keep
        their zeros); the wpad-cell discard margin owns all array-edge
        garbage. Traced values (axis_index)."""
        edges = 1 if cfg.bc == "edges" else 0
        bounds = []
        for d, name in enumerate(axis_names):
            M = padded_shape[d]
            if periodic:  # torus: no global edge, every shard is inner
                bounds.extend([jnp.int32(-1), jnp.int32(M)])
                continue
            coord = jax.lax.axis_index(name)
            bounds.append(jnp.where(coord == 0, wpad - 1 + edges, -1))
            bounds.append(jnp.where(coord == axis_sizes[d] - 1,
                                    M - wpad - edges, M))
        return bounds

    def padded_multi(padded: jax.Array, wpad: int, ksteps: int) -> jax.Array:
        """Exchange the width-``wpad`` ghost ring, then run ``ksteps`` <=
        wpad fused steps; input AND output are the full padded shard (the
        output's ghost margins are garbage — the next exchange rewrites
        every margin cell before anything reads them). This is the
        pad-free core: the padded-carry solve path calls it directly so
        the per-exchange pad+crop copy of the whole block disappears.

        ``padded`` is the logical padded shard, or that shard at the
        origin of the kernel's aligned array (``carried_shapes``): the
        exchange and the freeze bounds then keep to the logical extent,
        and the kernel steps the aligned array with no pad or slice."""
        logical = tuple(n // s + 2 * wpad for s in axis_sizes)
        padded0 = halo_exchange_indep(
            padded, axis_names, axis_sizes, bc_value,
            staged=staged, width=wpad, periodic=periodic, logical=logical,
        )
        if use_pallas:
            bounds = _shard_bounds(logical, wpad)
            return ftcs_multistep_bounded_pallas(
                padded0, r, ksteps, jnp.stack(bounds).astype(jnp.int32))

        acc_dt = accum_dtype_for(padded.dtype)
        rr = jnp.asarray(r, acc_dt)
        if periodic:
            pinned = None  # torus: no Dirichlet ghosts, no frozen ring
        else:
            # global index of every padded cell; exterior (< 0 or >= n)
            # cells are true Dirichlet ghosts
            gidx = []
            for d, name in enumerate(axis_names):
                coord = jax.lax.axis_index(name)
                base = coord * (padded.shape[d] - 2 * wpad) - wpad
                gidx.append(base + jax.lax.broadcasted_iota(
                    jnp.int32, padded0.shape, d))
            exterior = functools.reduce(
                jnp.logical_or, [(g < 0) | (g > n - 1) for g in gidx])
            if cfg.bc == "edges":
                boundary = functools.reduce(
                    jnp.logical_or, [(g == 0) | (g == n - 1) for g in gidx])
                pinned = exterior | boundary
            else:
                pinned = exterior

        def mini_step(p):
            # clamp-pad so the outermost ring has *some* neighbor value; its
            # update is garbage but sits beyond every layer any valid cell
            # reads afterwards (periodic included: ghost layer L is valid
            # for the first wpad-L mini-steps, exactly when it is read)
            clamped = jnp.pad(p, 1, mode="edge")
            new = (p.astype(acc_dt)
                   + rr * laplacian_interior(clamped)).astype(p.dtype)
            if pinned is None:
                return new
            # exterior ghosts stay Dirichlet; edges-BC boundary ring stays
            # at its (never-changing) initial value
            return jnp.where(pinned, padded0, new)

        p = padded0
        for _ in range(ksteps):  # static unroll
            p = mini_step(p)
        return p

    def local_multi(local: jax.Array, w: int) -> jax.Array:
        out = padded_multi(halo_pad(local, bc_value, w), w, w)
        ctr = tuple(slice(w, -w) for _ in range(out.ndim))
        return out[ctr]

    return local_multi, padded_multi


def make_parity_machinery(cfg: HeatConfig, mesh):
    """Literal update-then-swap stepping (fortran/mpi+cuda/heat.F90:206-219).

    Unlike the default communication-avoiding order (exchange, then update),
    the reference updates every owned cell against the ghosts *as they are*,
    then swaps. That forces the ghost ring to be carried state: here the
    sharded global array is the PADDED field (each shard = owned + width-1
    ghosts), exactly the reference's ``(1-ng:nx+ng, 1-ng:ny+ng)`` per-rank
    allocation (:107).

    Ghost seeding decides whether the orders are distinguishable:
    - IC starts seed ghosts by one exchange — identical to the reference's
      whole-padded-array IC fill (``T = 2.0`` at :243 evaluates the IC at
      ghost coordinates too), so shipped-IC runs bit-match the default
      order (the equivalence round 1 claimed, now executable).
    - explicit-T0 starts seed ghosts with ``bc_value`` only (nothing fills
      them, as in a raw restart): the first update reads stale ghosts and
      the two orders genuinely diverge — the reference's latent
      stale-first-step behavior, made observable.

    Returns (seed, advance, crop): seed builds the padded global from the
    owned global, advance runs k literal steps, crop recovers the owned
    global.
    """
    axis_names = mesh.axis_names
    axis_sizes = mesh.devices.shape
    r = cfg.r
    bc_value = cfg.bc_value
    staged = cfg.comm == "staged"
    periodic = cfg.bc == "periodic"
    n = cfg.n
    spec = P(*axis_names)
    smap = functools.partial(shard_map, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, check_vma=False)

    def _pinned_mask(padded):
        # cells the update must never change: the ghost ring itself (w=1),
        # plus the global boundary ring under "edges" semantics
        gidx = []
        for d, name in enumerate(axis_names):
            coord = jax.lax.axis_index(name)
            base = coord * (padded.shape[d] - 2) - 1
            gidx.append(base + jax.lax.broadcasted_iota(
                jnp.int32, padded.shape, d))
        ghost = functools.reduce(
            jnp.logical_or, [(g < 0) | (g > n - 1) for g in gidx])
        if cfg.bc == "edges":
            ring = functools.reduce(
                jnp.logical_or, [(g == 0) | (g == n - 1) for g in gidx])
            return ghost | ring
        return ghost

    def local_parity_step(padded):
        acc_dt = accum_dtype_for(padded.dtype)
        rr = jnp.asarray(r, acc_dt)
        lap = laplacian_interior(padded)  # owned region, reading ghosts
        new = padded.astype(acc_dt)
        ctr = tuple(slice(1, -1) for _ in range(padded.ndim))
        new = new.at[ctr].add(rr * lap)
        new = jnp.where(_pinned_mask(padded), padded,
                        new.astype(padded.dtype))
        # ghost update AFTER the stencil — the literal :218 ``call swap()``
        return halo_exchange_indep(new, axis_names, axis_sizes, bc_value,
                                   staged=staged, width=1, periodic=periodic)

    def seed(T_owned: jax.Array, from_ic: bool) -> jax.Array:
        def body(local):
            padded = halo_pad(local, bc_value, 1)
            if from_ic:
                padded = halo_exchange_indep(padded, axis_names, axis_sizes,
                                             bc_value, staged=staged, width=1,
                                             periodic=periodic)
            return padded

        return jax.jit(smap(body))(T_owned)

    @functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
    def advance(Tp, k: int):
        def body(padded):
            return jax.lax.fori_loop(
                0, k, lambda i, t: local_parity_step(t), padded)

        return smap(body)(Tp)

    @jax.jit
    def crop(Tp):
        return smap(
            lambda p: p[tuple(slice(1, -1) for _ in range(p.ndim))])(Tp)

    return seed, advance, crop


def _solve_parity(cfg: HeatConfig, T0, mesh, fetch: bool, warm_exec: bool):
    """Parity-ordered solve path (cfg.parity_order)."""
    if cfg.checkpoint_every:
        raise ValueError(
            "parity_order is a bit-parity experiment mode and does not "
            "support checkpointing (the carried state is the padded field)")
    master_print("step ordering: update-then-swap "
                 "(reference parity, mpi+cuda/heat.F90:206-219)")
    sharding = NamedSharding(mesh, P(*mesh.axis_names))
    T_owned, start_step = resolve_initial_field(cfg, T0, sharding=sharding)
    seed, advance, crop = make_parity_machinery(cfg, mesh)
    Tp = seed(T_owned, from_ic=T0 is None)
    res = drive(cfg.with_(report_sum=False), Tp, advance,
                start_step=start_step, fetch=False, warm_exec=warm_exec)
    return _finalize_carried(cfg, res, crop, fetch)


def _finalize_carried(cfg: HeatConfig, res, crop, fetch: bool):
    """Crop a padded-state result back to the owned field and do the
    post-solve accounting (fetch, gsum) the padded state deferred."""
    res.cfg = cfg
    res.T_dev = crop(res.T_dev)
    res.T = host_fetch(res.T_dev) if fetch else None
    if cfg.report_sum:
        if res.T is not None:
            res.gsum = float(np.sum(np.asarray(res.T, np.float64)))
            res.gsum_dtype = "float64"
        else:
            acc = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            res.gsum = float(np.asarray(jnp.sum(res.T_dev, dtype=acc)))
            res.gsum_dtype = np.dtype(acc).name
    return res


# auto depths above this get the compile guard. Round-4 measured cold
# Mosaic compile times for the auto-picked kernels (chipless
# AOT-topology bisect, benchmarks/compile_bisect_topology*.json):
# flagship-scale fused kernels cost MINUTES cold (16384-local: k=8
# 393 s, k=16 980-2038 s on the TOPOLOGY path vs 471 s live, k=32
# 665 s), and the thin-band deep-unroll family is a genuine cliff
# (8192-local k=32 wedged >36 min before being killed). Round 5 capped
# the auto 2D depth at the kernel's per-pass chunk (16 at flagship
# width — the measured rate optimum), which removes the wedge family
# from the auto path's reach entirely: every auto program now cold-
# compiles in bounded minutes on the live path, so depths <= 16 stay
# unguarded (see _guard_fuse_compile).
_SAFE_FUSE = 16

# Default probe wall budget. Sized ABOVE every measured cold compile of a
# program the auto planner can pick — the guard exists to catch the
# genuinely wedged family (thin-band deep unroll: >36 min before being
# killed), not to time out legitimate flagship compiles. Slowest measured
# compiles of programs still built, all at flagship scale (16384-local,
# Pallas): depth 8 in ~6-16 min (compile_bisect_topology.json), depth 16
# 471 s live and up to 2038 s on the host-contended chipless topology
# path (benchmarks/kernel_census.py). No measured compile now needs
# 2400 s; the value stays until a measured compile retunes it.
_DEFAULT_BUDGET_S = "2400"


@dataclasses.dataclass
class GuardReport:
    """Compile-guard telemetry, attached to ``SolveResult.guard`` whenever
    the guard probed (VERDICT r4 #8: a timed-out probe's cost — and what
    became of the abandoned compile — must be visible in the result a
    bench row consumes, never silently folded away)."""
    probed: bool = False
    timed_out: bool = False
    budget_s: float = 0.0
    probe_s: float = 0.0               # wall cost, folded into compile_s
    orphan: Optional[str] = None       # timeout only: "left_running" — the
    #                                    abandoned compile persists until it
    #                                    finishes or the process exits
    degraded: Optional[dict] = None    # cfg fields the fallback rewrote


def _bounded_compile(fn, budget_s: float):
    """Run ``fn`` (an XLA/Mosaic compile) in a daemon thread with a wall
    budget. Returns (result, None) on success, (None, "timeout") when the
    budget expires — the thread is left running (a C++ compile cannot be
    interrupted from Python; it dies with the process or finishes into
    the persistent compile cache). fn's exceptions propagate."""
    box: dict = {}

    def run():
        try:
            box["r"] = fn()
        except BaseException as e:  # noqa: BLE001 — reraised below
            box["e"] = e

    t = threading.Thread(target=run, daemon=True, name="heat-compile-guard")
    t.start()
    t.join(budget_s)
    if t.is_alive():
        return None, "timeout"
    if "e" in box:
        raise box["e"]
    return box.get("r"), None


def _compile_probe(cfg: HeatConfig, mesh, kf: int, remaining: int,
                   padded: bool) -> dict:
    """AOT-compile every program drive() will run — each chunk size from
    the SAME derivation drive uses (common.chunk_sizes: a remainder chunk
    still unrolls the deep-fused kernel and is a distinct XLA program) —
    on the path's actual global state shape. No device buffers, no data
    transfer. Returns {chunk_size: compiled executable}; the caller hands
    it to drive(precompiled=...) so the probe's work is never repeated."""
    from .common import chunk_sizes

    # The persistent compile cache is set on the main thread before the
    # probe starts (cli.main; the guard's ensure_cache_env), so an
    # abandoned (timed-out) probe's eventual completion pays forward to a
    # rerun. Process-global jax config is never touched from the probe
    # thread: that would race the main thread.
    if padded:
        _, advance, _ = make_padded_carry_machinery(cfg, mesh)
    else:
        advance = make_advance(cfg, mesh)
    struct = _probe_state_struct(cfg, mesh, kf, padded)
    return {k: advance.lower(struct, k).compile()
            for k in chunk_sizes(cfg, remaining)}


def _probe_state_struct(cfg: HeatConfig, mesh, kf: int, padded: bool):
    """The sharded state ShapeDtypeStruct the probe compiles against."""
    sizes = mesh.devices.shape
    shape = (tuple(c * int(s) for c, s in
                   zip(carried_shapes(cfg, sizes, kf)[1], sizes))
             if padded else cfg.shape)
    return jax.ShapeDtypeStruct(
        shape, jnp_dtype(cfg.dtype),
        sharding=NamedSharding(mesh, P(*mesh.axis_names)))


def _agree_any_timeout(timed_out: bool) -> bool:
    """Multi-process agreement on the guard verdict: every process must
    run the SAME advance program (different fuse depths mean different
    halo widths and different collective sequences — a mismatched SPMD
    program hangs the job), so if ANY process's probe timed out, all fall
    back together. Mirrors _agree_resume_step's minimum rule."""
    if jax.process_count() <= 1:
        return timed_out
    from jax.experimental import multihost_utils

    flags = np.asarray(multihost_utils.process_allgather(
        jnp.asarray(int(timed_out), jnp.int32)))
    agreed = bool(flags.max())
    if agreed != timed_out:
        master_print("compile guard: a peer process's probe timed out — "
                     "falling back job-wide")
    return agreed


def _guard_platform_ok() -> bool:
    """The guard only pays for itself where Mosaic compiles can cliff
    (TPU); CPU interpret-mode 'compiles' are trivially bounded. A seam so
    tests can force the guard on without patching jax.default_backend
    globally (which would flip the kernels' interpret-mode detection)."""
    return jax.default_backend() == "tpu"


def _guard_fuse_compile(cfg: HeatConfig, mesh, remaining: int,
                        padded: bool = True):
    """Bound the compile time of the AUTO-selected fuse depth.

    The planner's k* (fuse_depth_sharded) is a throughput optimum with no
    compile-cost term, and deep-unroll Mosaic compiles can cliff (the
    col-tiled band cap note in ops/pallas_stencil.py documents minutes-to
    ->12-minutes growth). A user running the default config must never
    stall unboundedly in compile, so: when the depth was auto-picked and
    exceeds the measured-safe depth, every program drive() will compile is
    compiled under one wall budget (``HEAT_COMPILE_BUDGET_S``, default
    ``_DEFAULT_BUDGET_S`` = 2400 s — sized above the slowest measured
    legitimate cold compile; 0 disables); on timeout the solve falls back
    to the
    seconds-compiling XLA local kernel with a loud warning, job-wide
    (_agree_any_timeout), and the abandoned Mosaic compile finishes into
    the persistent cache (a rerun gets the kernel for free if it does
    complete). Explicit --fuse-steps or --local-kernel pallas is honored
    unguarded — the user asked for that exact program.

    Returns ``(cfg, precompiled, report)``: on success ``precompiled``
    carries the probe's executables for drive(precompiled=...), so the
    guard costs zero extra compiles; ``report`` is a :class:`GuardReport`
    whose ``probe_s`` is the probe's wall time (drive folds it into the
    reported compile/total time — the guard must not make minutes of
    compile invisible to timing consumers) and whose ``orphan`` field
    records what became of an abandoned compile.

    The probe compiles in-process, in a thread (zero-copy executable
    hand-off; a timed-out compile keeps burning a core until it
    finishes): the guard runs only on a TPU parent, which holds the chip
    and libtpu's lock, so a child process could not load libtpu.

    Divergence safety: every gate before the collective agreement derives
    from cfg/mesh/platform — identical across an SPMD job by contract.
    Per-host state that CAN diverge (the budget env var, probe exceptions,
    probe timing) only feeds the agreed verdict, never whether the
    collective is reached — a process skipping a collective its peers
    entered would hang the job."""
    t0 = time.perf_counter()
    kf = fuse_depth_sharded(cfg, mesh.devices.shape)
    # Trigger is kf > _SAFE_FUSE: the per-pass chunk cap keeps the auto
    # path out of the >36-min wedge family — wide shards cap at k=16,
    # whose live cold compile measured a bounded 471 s in round 5 — and
    # bounded-minutes compiles are not the stall the guard exists for.
    # Auto depths > 16 only arise for narrow shards (chunk cap 32, small
    # bands) and keep the guard as belt-and-braces.
    if (cfg.fuse_steps or kf <= _SAFE_FUSE or remaining <= 0
            or cfg.local_kernel != "auto" or cfg.dtype == "float64"
            or not _guard_platform_ok()):
        # nothing to guard: explicit user program (a requested
        # --local-kernel pallas must never be silently downgraded to xla
        # — that IS the "wait the compile out" remedy the fallback
        # warning advertises), capped auto depth, or the XLA/f64 path
        # (seconds-fast compiles) already chosen
        return cfg, None, GuardReport()
    try:
        budget = float(os.environ.get("HEAT_COMPILE_BUDGET_S",
                                      _DEFAULT_BUDGET_S))
    except ValueError:
        budget = float(_DEFAULT_BUDGET_S)
    rep = GuardReport(budget_s=budget)
    pre, timed_out = None, False
    if budget > 0:  # budget<=0 disables the probe, NOT the agreement
        rep.probed = True  # only now: a budget-0 run never probed, and
        # its SolveResult must not carry a report claiming it did
        from ..utils import ensure_cache_env

        # flagship-scale compiles are exactly when the persistent cache
        # pays: the probe's compiles and drive's own land where a rerun
        # finds them
        ensure_cache_env()
        try:
            pre, err = _bounded_compile(
                lambda: _compile_probe(cfg, mesh, kf, remaining, padded),
                budget)
            if err is not None:
                timed_out, rep.orphan = True, "left_running"
        except Exception as e:  # noqa: BLE001 — a probe crash (e.g.
            # RESOURCE_EXHAUSTED on the deep unroll) means the k*
            # program is unusable here: fall back rather than let
            # drive hit the same error, and NEVER skip the agreement
            # below (peers would hang in the collective)
            master_print(f"compile guard: probe failed "
                         f"({type(e).__name__}: {e}); treating as "
                         f"timeout")
            pre, timed_out = None, True
    # rep.timed_out carries the AGREED verdict (the one that drives the
    # degrade), which can differ from the local probe's outcome job-wide
    rep.timed_out = _agree_any_timeout(timed_out)
    if not rep.timed_out:
        rep.probe_s = time.perf_counter() - t0
        return cfg, pre, rep
    # Fallback must be a program whose compile is KNOWN fast. Shallower
    # Pallas depths are not that: at flagship scale even k=8 cold-compiles
    # in ~6-16 min (compile_bisect_topology.json), so a k=16 fallback
    # would bust the very budget that just expired. The XLA local kernel
    # compiles in seconds at every measured size (same fused exchange
    # structure, ~5x lower per-step throughput) — a slower solve that
    # starts now beats a fast one stuck in Mosaic.
    # Pin the probed depth too: the xla kernel is exempt from the
    # round-5 per-pass chunk cap, so leaving fuse_steps=0 would silently
    # recompute a DIFFERENT depth (flagship: 32 vs the probed 16) and
    # the "same fuse depth" the warning promises — and the exchange
    # cadence/ghost widths any telemetry shows — would not match the
    # program that runs (review r5).
    degrade = {"local_kernel": "xla", "fuse_steps": kf}
    if rep.orphan == "left_running":
        orphan_note = (
            "The abandoned Mosaic compile continues and lands in the "
            "persistent compile cache — a rerun may pick the kernel up "
            "instantly.")
    elif pre is not None:
        # a peer's timeout forced the job-wide fallback but THIS process's
        # probe compile completed — the local cache is already warm
        orphan_note = ("This process's own probe compile completed (a "
                       "peer's timeout forced the job-wide fallback); the "
                       "local compile cache is warm.")
    else:  # probe crashed / failed before compiling anything: there is
        # no background compile and no cache entry to wait for
        orphan_note = "No probe compile was started."
    master_print(
        f"WARNING: auto fuse depth {kf} (Pallas kernel) did not compile "
        f"within {rep.budget_s:.0f}s (HEAT_COMPILE_BUDGET_S); falling back "
        f"to local_kernel='xla' at the same fuse depth — compiles in "
        f"seconds, ~5x lower per-step throughput. {orphan_note} "
        f"Pass --local-kernel pallas to wait the compile out.")
    rep.degraded = degrade
    rep.probe_s = time.perf_counter() - t0
    return cfg.with_(**degrade), None, rep


def _solve_padded_carry(cfg: HeatConfig, T0, mesh, fetch: bool,
                        warm_exec: bool, two_point_repeats: int = 0):
    """Default sharded solve: padded-carry state (make_padded_carry_machinery)."""
    cfg, pre, guard = _guard_fuse_compile(cfg, mesh, cfg.ntime, padded=True)
    sharding = NamedSharding(mesh, P(*mesh.axis_names))
    T_owned, start_step = resolve_initial_field(cfg, T0, sharding=sharding)
    # The guard's probe ran BEFORE the field resolved, with
    # remaining=cfg.ntime — correct only while this path never resumes
    # (checkpointed runs take the owned-state path). Fail loudly if a
    # future routing change breaks that convention, rather than silently
    # probing a wrong remainder size and compiling the real one unguarded
    # inside drive (ADVICE r4).
    if start_step != 0:  # explicit raise: an assert vanishes under -O,
        # silently restoring the wrong-remainder-probe hole
        raise RuntimeError(
            "padded-carry path resumed from a checkpoint (start_step="
            f"{start_step}) — the compile guard probed the wrong remainder; "
            "route resumes through the owned-state path")
    seed, advance, crop = make_padded_carry_machinery(cfg, mesh)
    Tp = seed(T_owned)
    del T_owned  # unpin the owned-field device buffer for the solve
    res = drive(cfg.with_(report_sum=False), Tp, advance,
                start_step=start_step, fetch=False, warm_exec=warm_exec,
                two_point_repeats=two_point_repeats, precompiled=pre,
                precompile_s=guard.probe_s)
    res.guard = (guard if guard.probed or guard.degraded else None)  # a
    # peer-agreed degrade with a local budget of 0 still must be visible
    return _finalize_carried(cfg, res, crop, fetch)


def fuse_depth_sharded(cfg: HeatConfig, axis_sizes) -> int:
    """Halo width per exchange: requested fuse depth capped by the smallest
    local extent (a shard can't lend deeper halo than it owns) and by the
    local kernel's per-pass fusion cap for the rank.

    Auto depth balances the k-dependent costs per owned point-step:
    per-exchange overhead (~1/k per step — on the default padded-carry
    path that is the collective dispatch + the exchange breaking kernel
    fusion, no longer a pad+crop copy) against redundant margin work
    growing as ~2*d*k/L — minimized at k* = sqrt(L/d), then capped at
    the local KERNEL's per-pass chunk depth in BOTH ranks: fusing deeper
    than the kernel consumes per pass saves only collective dispatches
    (the HBM passes don't amortize further) while still paying 2*d*k
    margin compute on wider ghosts.

    The 2D cap is round-5 MEASURED, not just modeled: the round-2 sweep
    that crowned k=32 (k=8/16/32 -> 94/98/112% roofline) predates the
    round-4 ``_thin_chunk_cap``, which executes k=32 as two 16-deep
    passes at flagship width; with that cap in place the on-chip 4-point
    curve (benchmarks/collective_overhead.json, 2026-08-01) inverts the
    optimum: k=16 -> 1.571e11, k=32 -> 1.399e11 (12% loss) at 16384^2
    f32. 3D clamps at _KMAX_3D (=8) for the same reason (for realistic
    3D shards sqrt(L/d) <= 8 anyway: 512^3 over 2x2x2 gives k*=9->8).
    An EXPLICIT fuse_steps is honored either way (capped only by the
    local extent) — the A/B labs must be able to pin any depth — and a
    CONFIGURED xla local kernel has no per-pass chunk, so its auto depth
    keeps the plain sqrt form (including dtype float64, which can never
    run the Pallas kernel and always resolves to xla). The cap keys on
    the configured kernel deliberately: local_kernel='auto' keeps the
    cap even on hosts where auto resolves to xla at runtime (CPU tests,
    the 8-device dryrun), so chipless runs exercise the same exchange
    structure the TPU default compiles — structural fidelity over a
    perf optimum no one measures off-chip."""
    from ..ops.pallas_stencil import _KMAX_3D

    kmax = _KMAX_2D if cfg.ndim == 2 else _KMAX_3D
    local_min = min(cfg.n // s for s in axis_sizes)
    want = cfg.fuse_steps
    if not want:
        want = max(1, min(kmax, round((local_min / cfg.ndim) ** 0.5)))
        if (cfg.ndim == 2 and cfg.local_kernel != "xla"
                and cfg.dtype != "float64"):
            want = min(want, _auto_chunk_2d(cfg, axis_sizes))
    return max(1, min(want, local_min))


def _auto_chunk_2d(cfg: HeatConfig, axis_sizes) -> int:
    """Per-pass chunk depth of the 2D kernel the planner will SELECT for
    this shard, evaluated at the ghost-PADDED shape the kernel actually
    sees (deepest candidate ghost allowance — near the band threshold
    the unpadded width under-reports: local 4864 reads cap=32 unpadded
    but the (4864+64)-wide runtime array chunks at 16). Sole consumer:
    ``fuse_depth_sharded``'s depth cap. (A round-5 interim also fed a
    guard wide-band signal from here; the guard reverted to depth-only
    gating once the probe's topology-compile cost was measured — see
    ``_guard_fuse_compile``.)"""
    from ..ops.pallas_stencil import effective_chunk_2d

    rows = cfg.n // axis_sizes[0] + 2 * _KMAX_2D
    cols = cfg.n // axis_sizes[-1] + 2 * _KMAX_2D
    return effective_chunk_2d((rows, cols), cfg.dtype)


def _fused_blocks(block, t, count: int, paired: bool):
    """``count`` applications of ``block`` in a loop, two an iteration if
    ``paired``. A block that ends in a kernel call on the whole carried
    array (the aligned carry, ``carried_shapes``) cannot step in place: the
    kernel reads its neighbours' halo rows, so it writes a fresh buffer,
    and with one block a body XLA copies that buffer back into the loop
    carry after every block. With two, the first block's input is dead
    when the second writes, so the second writes the carry's own buffer;
    an odd count peels one block before the loop. Elsewhere one block a
    body is kept: the XLA local kernel's loop gains copies when paired."""
    if not paired:
        if count:
            t = jax.lax.fori_loop(0, count, lambda i, t: block(t), t)
        return t
    if count % 2:
        t = block(t)
    if count // 2:
        t = jax.lax.fori_loop(0, count // 2, lambda i, t: block(block(t)), t)
    return t


def _chunked_advance(mesh, step, kf: int, paired: bool = False):
    """Jitted, donated k-step advance: fused blocks of ``kf`` steps + one
    remainder call, via ``step(local_state, nsteps)`` inside shard_map —
    the ONE chunking scheme both the owned-state and padded-carry paths
    use (only the step callable differs). ``paired`` as in
    ``_fused_blocks``."""
    spec = P(*mesh.axis_names)

    @functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
    def advance(Tg, k: int):
        def body(local):
            n_fused, rem = divmod(k, kf)
            local = _fused_blocks(lambda t: step(t, kf), local, n_fused,
                                  paired)
            if rem:
                local = step(local, rem)
            return local

        return shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(Tg)

    return advance


def make_advance(cfg: HeatConfig, mesh):
    local_multi, _ = make_local_multistep(
        cfg, mesh.axis_names, mesh.devices.shape)
    kf = fuse_depth_sharded(cfg, mesh.devices.shape)
    return _chunked_advance(mesh, local_multi, kf)


def carried_shapes(cfg: HeatConfig, axis_sizes, kf: int) -> tuple:
    """``(logical, carried)`` local shapes of one shard on the padded-carry
    path at halo width ``kf``. ``logical`` is the padded shard: the owned
    cells and a kf-wide ghost margin on every side. ``carried`` is the
    array that holds it, with the logical shard at its origin: the
    thin-band kernel's aligned shape where each shard steps with that
    kernel (``thin_carry_shape``), so a fused block runs no pad or slice
    of the whole shard; the logical shape everywhere else (the XLA local
    kernel, the col-tiled plan, 3D)."""
    logical = tuple(cfg.n // int(s) + 2 * kf for s in axis_sizes)
    if cfg.ndim == 2 and _local_kernel_is_pallas(cfg):
        return logical, thin_carry_shape(logical, cfg.dtype, kf) or logical
    return logical, logical


def _carry_seed_crop(bc_value, kf: int, logical, carried):
    """Per-shard bodies of the padded-carry entry and exit: ``seed`` pads
    the owned shard once with ``bc_value`` ghosts and zero alignment
    cells (``carried_shapes``), ``crop`` cuts the owned shard back out."""
    align = [(0, c - l) for l, c in zip(logical, carried)]

    def seed(local):
        padded = halo_pad(local, bc_value, kf)
        return jnp.pad(padded, align) if carried != logical else padded

    def crop(p):
        return p[tuple(slice(kf, l - kf) for l in logical)]

    return seed, crop


def make_padded_carry_machinery(cfg: HeatConfig, mesh):
    """(seed, advance, crop) carrying the PADDED field as solve state.

    The classic advance pays a pad+crop copy of every local block per
    exchange (~2/k full-field HBM passes at fuse depth k). Carrying each
    shard as owned+2w cells removes both copies: every fused block is
    exchange-in-place + kernel, ghosts garbage between exchanges but
    rewritten before any read. Owned-cell values are bit-identical to the
    classic path (same exchange, same kernel, same bounds). The same
    padded-state idea the parity machinery uses for w=1 ghosts
    (make_parity_machinery), here at the communication-avoiding width.
    Where each shard steps with the thin-band kernel, the shard is carried
    in that kernel's aligned shape from seed to crop (``carried_shapes``),
    so the kernel's own per-call pad and slice of the shard go too.
    """
    axis_names = mesh.axis_names
    axis_sizes = mesh.devices.shape
    _, padded_multi = make_local_multistep(cfg, axis_names, axis_sizes)
    kf = fuse_depth_sharded(cfg, axis_sizes)
    logical, carried = carried_shapes(cfg, axis_sizes, kf)
    seed_local, crop_local = _carry_seed_crop(cfg.bc_value, kf, logical,
                                              carried)
    spec = P(*axis_names)
    smap = functools.partial(shard_map, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, check_vma=False)

    def seed(T_owned: jax.Array) -> jax.Array:
        # the caller must drop its T_owned reference after seeding (see
        # _solve_padded_carry): the owned-field buffer (1 GiB at 16384^2
        # f32) must not stay pinned for the whole solve alongside the
        # padded state. (Donation can't help here — the padded output is a
        # different shape, so the input buffer is never reusable and JAX
        # warns.)
        return jax.jit(smap(seed_local))(T_owned)

    # margins stay width kf across calls; only the step count shrinks on
    # the remainder chunk
    advance = _chunked_advance(mesh, lambda p, k: padded_multi(p, kf, k), kf,
                               paired=carried != logical)
    # drive() counts the kernels' point-steps and the exchanges per chunk
    advance.points_computed = functools.partial(
        carried_points, cfg, axis_sizes, kf)
    advance.halo_counts = functools.partial(
        carried_halo_counts, cfg, axis_sizes, kf)

    @jax.jit
    def crop(Tp):
        return smap(crop_local)(Tp)

    return seed, advance, crop


def _carried_blocks(k: int, kf: int) -> list:
    """(steps, count) of the fused blocks ``_chunked_advance`` runs for a
    chunk of ``k`` steps: ``k // kf`` blocks of kf and one of the rest."""
    n_fused, rem = divmod(k, kf)
    return [(s, c) for s, c in ((kf, n_fused), (rem, 1)) if s and c]


def carried_points(cfg: HeatConfig, axis_sizes, kf: int,
                   k: int) -> int:
    """Point-steps the local kernels of all shards compute for one
    padded-carry chunk of ``k`` steps: each block steps the whole padded
    shard (owned cells plus the kf-wide ghost margins), the Pallas kernel
    by its own plan (band halo rows and alignment padding included), the
    XLA step cell by cell."""
    # the kernel's plan is the logical shard's, whichever array carries it
    shape = carried_shapes(cfg, axis_sizes, kf)[0]
    pallas = _local_kernel_is_pallas(cfg)
    per_block = 0
    for steps, count in _carried_blocks(k, kf):
        per_block += count * (
            multistep_points(shape, cfg.dtype, steps) if pallas
            else int(np.prod(shape)) * steps)
    return int(np.prod(axis_sizes)) * per_block


def carried_halo_counts(cfg: HeatConfig, axis_sizes, kf: int,
                        k: int) -> tuple:
    """``(exchanges, bytes, lane_cuts)`` one device runs and sends for a
    padded-carry chunk of ``k`` steps: one kf-wide exchange a fused block,
    the bytes averaged over the devices (``halo.exchange_bytes``), whose
    slabs span the logical shard whichever array carries it, and the
    exchanges whose column slabs the Pallas strip copy cuts: all of them
    where the carried shard fits its tiles (``halo.lane_cut_plan``), else
    none."""
    logical, carried = carried_shapes(cfg, axis_sizes, kf)
    dtype = jnp.dtype(jnp_dtype(cfg.dtype))
    exchanges = sum(c for _, c in _carried_blocks(k, kf))
    per = exchange_bytes(logical, axis_sizes, kf, dtype.itemsize,
                         periodic=cfg.bc == "periodic")
    cut = lane_cut_plan(carried, dtype, kf, logical) is not None
    return exchanges, exchanges * per, exchanges if cut else 0


def make_mega_machinery(cfg: HeatConfig, mesh):
    """(seed, advance, crop, kf): the padded-carry machinery wrapped in the
    SERVE dispatch contract (serve/engine.py MegaLaneEngine) — one request
    spanning the whole device mesh as a *mega-lane*.

    ``advance(Tp, rem, k)`` runs ``k`` steps of the exact chunked body the
    solo sharded ``drive()`` compiles (``divmod(k, kf)`` fused blocks of
    the communication-avoiding ``padded_multi`` plus one remainder block —
    owned-cell values are bit-identical under ANY chunk partition, the
    same margin argument that makes fused exchanges bit-identical to
    every-step exchanges) and returns ``(Tp', rem', boundary)``:

    - ``Tp`` is donated (the solo drive's double-buffer ping-pong);
    - ``rem`` is an undonated ``(1,)`` int32 countdown — ``rem' =
      max(rem - k, 0)``, the same algebra the lane engine's per-lane
      countdown produces, so the scheduler's host mirror predicts it;
    - ``boundary`` is the ``(K_BOUNDARY, 1)`` int32 vector of
      [remaining; isfinite; bitcast numerics stats] the serve
      scheduler's boundary fetch expects (serve/engine.BOUNDARY_ROWS) —
      the finite bit and the stats reduced over OWNED cells only (each
      shard contributes its interior verdict through the same shard_map
      program; the garbage ghost margins between exchanges never vote),
      so mega-lane health AND solution quality ride the boundary D2H
      exactly like a packed lane's. The chunk's final step runs as its
      own fused block so the pre-step owned cells are in scope for the
      residual stat — owned-cell invariance under chunk partitioning
      (the margin argument above) keeps the field bytes unchanged.

    ``seed``/``crop`` are the padded-carry entry/exit programs, returned
    un-jit-called so the serve engine can AOT-compile them once per
    (config, mesh) and reuse across admissions."""
    axis_names = mesh.axis_names
    axis_sizes = mesh.devices.shape
    _, padded_multi = make_local_multistep(cfg, axis_names, axis_sizes)
    kf = fuse_depth_sharded(cfg, axis_sizes)
    logical, carried = carried_shapes(cfg, axis_sizes, kf)
    seed_local, crop_local = _carry_seed_crop(cfg.bc_value, kf, logical,
                                              carried)
    nd = cfg.ndim
    spec = P(*axis_names)
    smap = functools.partial(shard_map, mesh=mesh, in_specs=(spec,),
                             check_vma=False)

    seed = jax.jit(smap(seed_local, out_specs=spec))

    from ..serve.engine import pack_boundary

    @functools.partial(jax.jit, static_argnums=2, donate_argnums=0)
    def advance(Tp, rem, k: int):
        def body(padded):
            if k > 1:
                n_fused, r_ = divmod(k - 1, kf)
                padded = _fused_blocks(lambda t: padded_multi(t, kf, kf),
                                       padded, n_fused, carried != logical)
                if r_:
                    padded = padded_multi(padded, kf, r_)
            prev = padded
            # the chunk's final step is its own fused block so the
            # pre-step owned cells feed the residual stat; owned cells
            # are invariant under chunk partitioning, so the field
            # bytes match the one-shot chunk body exactly
            padded = padded_multi(padded, kf, 1)
            ctr = tuple(slice(kf, l - kf) for l in logical)
            # per-shard owned-interior health bit + numerics stats:
            # reading only (never writing) the stepped state, so
            # bit-identity is untouched — the PR-5 lane-engine
            # argument, one mesh wide
            own = padded[ctr].astype(jnp.float32)
            one = (1,) * nd
            fin = jnp.isfinite(padded[ctr]).all().reshape(one)
            resid = jnp.abs(own - prev[ctr].astype(jnp.float32)
                            ).max().reshape(one)
            return (padded, fin, resid, own.min().reshape(one),
                    own.max().reshape(one), own.sum().reshape(one))

        Tp, fins, resid, tmin, tmax, heat = shard_map(
            body, mesh=mesh, in_specs=(spec,), out_specs=(spec,) * 6,
            check_vma=False)(Tp)
        rem2 = jnp.maximum(rem - k, 0)
        finite = jnp.all(fins).astype(rem2.dtype).reshape((1,))
        # cross-shard merge: max/min/max/sum over the per-shard partials
        stats = jnp.stack([resid.max(), tmin.min(), tmax.max(),
                           heat.sum()]).astype(jnp.float32).reshape(4, 1)
        return Tp, rem2, pack_boundary(rem2, finite, stats)

    crop = jax.jit(smap(crop_local, out_specs=spec))
    return seed, advance, crop, kf


@register("sharded")
def solve(cfg: HeatConfig, T0: Optional[np.ndarray] = None, mesh=None,
          fetch: bool = True, warm_exec: bool = False,
          two_point_repeats: int = 0, **_) -> SolveResult:
    mesh = mesh or build_mesh(cfg.ndim, cfg.mesh_shape)
    validate_divisible(cfg.n, mesh)
    master_print(f"Automatic mesh decomposition: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    # local block dims + shard->device binding, the reference's per-rank
    # announcements (local nx/ny at mpi+cuda/heat.F90:239-240, rank->GPU at
    # :69), gated master-only like every other stdout line
    local = tuple(cfg.n // s for s in mesh.devices.shape)
    master_print("local block: " + " x ".join(str(v) for v in local))
    flat = list(np.ndenumerate(mesh.devices))
    for coords_d, dev in flat[:32]:
        master_print(f"  mesh {coords_d} -> device {dev.id} "
                     f"(process {getattr(dev, 'process_index', 0)})")
    if len(flat) > 32:
        master_print(f"  ... ({len(flat) - 32} more shards)")

    if cfg.checkpoint_every:
        # announce the I/O contract up front, like the mesh decomposition:
        # on a multi-host job the async writer persists each process's own
        # shards (checkpoint.save_shards) from a device-side snapshot while
        # stepping continues — same snapshot-and-continue contract as the
        # single-host global dump
        master_print("checkpoint I/O: "
                     + ("async snapshot-and-continue (bounded queue depth "
                        "2; --async-io off for the sync fallback)"
                        if cfg.use_async_io() else "sync (--async-io off)"))
    if cfg.parity_order:
        res = _solve_parity(cfg, T0, mesh, fetch, warm_exec)
    elif not cfg.checkpoint_every and not cfg.check_numerics and cfg.ntime:
        # default fast path: padded-carry state (no per-exchange pad+crop
        # copies). Checkpoint/numerics runs keep the owned-state path:
        # their boundary events need the OWNED field (a padded-state
        # snapshot would persist garbage ghost margins), which padded
        # state only yields via a crop. The events themselves no longer
        # stall that path — drive's async pipeline snapshots on device
        # and resumes stepping (runtime/async_io.py) — so what the owned
        # path still pays vs this one is only the per-exchange pad+crop
        # copies, not the D2H+disk wall time.
        res = _solve_padded_carry(cfg, T0, mesh, fetch, warm_exec,
                                  two_point_repeats)
    else:
        # owned-state path (checkpoint / numerics runs): same auto fuse
        # depth, same deep-unroll kernel — guard it too, with the probe
        # compiling THIS path's program (owned global shape, and a
        # remaining count that respects checkpoint resume)
        sharding = NamedSharding(mesh, P(*mesh.axis_names))
        T, start_step = resolve_initial_field(cfg, T0, sharding=sharding)
        cfg, pre, guard = _guard_fuse_compile(
            cfg, mesh, cfg.ntime - start_step, padded=False)
        res = drive(cfg, T, make_advance(cfg, mesh), start_step=start_step,
                    fetch=fetch, warm_exec=warm_exec,
                    two_point_repeats=two_point_repeats, precompiled=pre,
                    precompile_s=guard.probe_s)
        res.guard = (guard if guard.probed or guard.degraded else None)  # a
    # peer-agreed degrade with a local budget of 0 still must be visible
    res.mesh_shape = tuple(mesh.devices.shape)
    res.mesh = mesh
    return res
