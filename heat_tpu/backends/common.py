"""Shared device-side solve driver: chunked jitted loop with heartbeat,
checkpointing, and timing.

The reference's hot loop is a host loop launching one kernel per step
(fortran/cuda_kernel/heat.F90:30-34). On TPU we instead compile a whole
*chunk* of steps into one ``lax.fori_loop`` program and call it repeatedly —
host involvement only at heartbeat/checkpoint boundaries, with the T/T_old
double buffer donated so XLA ping-pongs buffers with zero copies (replacing
the per-step ``T_old_d = T_d`` device memcpy at fortran/cuda_kernel/heat.F90:32).
"""

from __future__ import annotations

import math
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import HeatConfig
from ..runtime import async_io, checkpoint, debug, faults
from ..runtime import trace as trace_mod
from ..runtime.logging import master_print
from ..runtime.timing import Timing, sync, two_point_rate
from . import SolveResult


# --on-nan rollback: how many times the same flagged step may be retried
# before the blow-up is declared deterministic (a genuine CFL violation
# reproduces identically; a soft-error/injected NaN does not).
_MAX_ROLLBACKS_PER_STEP = 2


def _addressable(x) -> bool:
    """True when every shard of x lives on this process's devices.

    Injectable seam (tests fake a multi-host world by patching this): in a
    real multi-host job a mesh-sharded global array is NOT fully
    addressable and ``np.asarray`` on it raises — the same case
    ``timing.sync`` already guards."""
    return not isinstance(x, jax.Array) or x.is_fully_addressable


def host_fetch(x):
    """Fetch to host, or None when the array spans other processes (the
    caller must then use per-shard paths: ``io.write_soln_sharded``,
    ``checkpoint.save_shards``)."""
    return np.asarray(x) if _addressable(x) else None


def event_interval(cfg: HeatConfig) -> int:
    """Steps per device program: gcd of the host-visible event intervals."""
    ivals = [v for v in (cfg.heartbeat_every, cfg.checkpoint_every) if v > 0]
    if not ivals:
        return max(cfg.ntime, 1)
    g = ivals[0]
    for v in ivals[1:]:
        g = math.gcd(g, v)
    return g


def chunk_sizes(cfg: HeatConfig, remaining: int) -> list[int]:
    """Every step-count the drive loop will call ``advance`` with (at most
    two: the steady chunk and a final remainder). The ONE derivation both
    ``drive``'s warmup and the sharded compile guard's probe use — the
    guard must bound every program drive will compile, remainder included
    (a k=100 remainder still unrolls the same deep-fused kernel and is a
    distinct XLA program)."""
    if remaining <= 0:
        return []
    k0 = min(event_interval(cfg), remaining)
    sizes = {k0}
    if remaining % k0:
        sizes.add(remaining % k0)
    return sorted(sizes)


def aot_compile_chunks(advance, example, sizes, compiled=None, label=None,
                       kernel=None):
    """AOT-compile ``advance(example..., k)`` for every chunk size ``k``
    in ``sizes`` not already covered; returns ``(compiled, seconds)``.

    The ONE compile path for chunked step programs: ``drive``'s warmup and
    the serving engine's lane programs (serve/engine.py) both go through
    here, so no compile ever lands inside a timed region and compile
    bookkeeping (guard hand-off, serve's one-per-bucket accounting) stays a
    dict of size -> executable everywhere. Being the one path also makes
    it the compile observatory's tap (runtime/prof.py): every program
    actually built lands in the process-wide structured compile log with
    its ``label`` (caller-supplied key: bucket/tier for lanes, grid/dtype
    for solo solves), per-program wall, and first-vs-warm — the wall of a
    warm re-compile is the persistent compile cache's report card.

    ``example`` is a single array for the solo drive shape
    (``advance(T, k)``) or a TUPLE of arrays for multi-argument programs
    (the serve engine's ``advance(fields, r, n, remaining, k)``, which
    also returns the per-lane ``(2, L)`` boundary vector of remaining
    steps + isfinite bits — its leaves are donated selectively, which a
    single pytree argument cannot express); a tuple is splatted into
    ``lower``.

    ``kernel`` names the stepping body when one label can cover several
    (the serve lane programs compile both the XLA oracle and the Pallas
    lane kernels for the same bucket/tier — the compile log must tell
    them apart, or a Pallas-vs-XLA A/B reads as one warm cache key).
    """
    from ..runtime import prof

    compiled = dict(compiled or {})
    args = example if isinstance(example, tuple) else (example,)
    if label is None:
        shape = getattr(args[0], "shape", ())
        dtype = getattr(args[0], "dtype", "?")
        label = f"chunk {tuple(shape)} {dtype}"
    if kernel is not None:
        label = f"{label} [{kernel}]"
    t0 = time.perf_counter()
    for k in sizes:
        if k not in compiled:
            tk = time.perf_counter()
            compiled[k] = advance.lower(*args, k).compile()
            prof.compile_log().note(label, k, time.perf_counter() - tk)
    return compiled, time.perf_counter() - t0


def solo_program_specs():
    """Program-registry seam (ISSUE 13): the solo drive's chunked advance
    families as abstract ProgramSpecs — `heat-tpu audit` traces/lowers
    them on shape structs to check donation (the T/T_old double-buffer
    ping-pong this module's docstring promises), purity, dtype
    discipline, and digest drift, without running a solve."""
    from ..analysis.programs import ProgramSpec
    from ..utils import jnp_dtype

    def _spec(ndim, n, dtype, bc, steps=8):
        def build():
            from .xla import make_advance

            cfg = HeatConfig(n=n, ndim=ndim, dtype=dtype, bc=bc,
                             ntime=steps, backend="xla")
            adv = make_advance(cfg)
            T = jax.ShapeDtypeStruct(cfg.shape, jnp_dtype(dtype))
            return adv, (T, steps), (1,)

        return ProgramSpec(
            name=f"solo/xla/{ndim}d/n{n}/{dtype}/{bc}", build=build,
            donated=(0,), dtype=dtype,
            storage_round=(dtype == "bfloat16"), steps=steps,
            kernel="xla", family="solo")

    return [
        _spec(2, 48, "float32", "edges"),
        _spec(2, 48, "float32", "ghost"),
        _spec(2, 48, "float32", "periodic"),
        _spec(2, 48, "bfloat16", "edges"),
        _spec(2, 48, "float64", "ghost"),
        _spec(3, 16, "float32", "ghost"),
    ]


def drive(
    cfg: HeatConfig,
    T_dev: jax.Array,
    advance: Callable[[jax.Array, int], jax.Array],
    start_step: int = 0,
    to_host: Callable[[jax.Array], Optional[np.ndarray]] = host_fetch,
    warmup: bool = True,
    fetch: bool = True,
    warm_exec: bool = False,
    two_point_repeats: int = 0,
    precompiled: Optional[dict] = None,
    precompile_s: float = 0.0,
) -> SolveResult:
    """Run ``advance(T, k)`` (jitted, static k, donated T) to ``cfg.ntime``.

    Host-visible events (checkpoints, numerics flags) run through the
    asynchronous I/O pipeline by default (``cfg.async_io``,
    runtime/async_io.py): a checkpoint boundary costs one on-device buffer
    copy and stepping resumes immediately, with the D2H transfer and
    atomic-rename write in a bounded-queue background writer —
    backpressure (queue depth 2), drain on every exit path (no snapshot
    silently dropped), writer errors surfaced at the next boundary.
    ``--async-io off`` restores the inline sync->fetch->save stall.

    ``two_point_repeats > 0`` additionally measures the overhead-corrected
    two-point rate (``timing.two_point_rate`` — the headline benchmark's
    protocol) on a COPY of the final state, so the solve result is
    untouched; costs one extra buffer pair and 1 + 3*repeats extra chunk
    executions (warm + per-repeat single + back-to-back pair) — for
    benchmark configs the chunk is the whole solve, so budget device time
    accordingly.

    ``precompiled`` maps chunk size -> an already-compiled executable for
    ``advance`` (the sharded compile guard hands its probe's work forward
    so a guarded solve never compiles the same program twice); sizes it
    covers are skipped in warmup. ``precompile_s`` is the wall time the
    caller already spent producing them — folded into ``compile_s`` and
    ``total_s`` so guard minutes never vanish from the reported timing.

    The solve is one ``heat.drive.solve`` span (runtime/trace.py) around
    its compile, chunk dispatches, final fence and fetch; with the
    profiler on they sit beside the device's operations."""
    # request-scoped tracing (runtime/trace.py): the solo path records
    # into the process-global ring so `heat-tpu run --trace` puts chunk
    # dispatches, checkpoint snapshots, and the background writer's
    # D2H+publish spans (the PR-1 overlap) on one Perfetto timeline.
    tracer = trace_mod.get_tracer()
    drv_track = tracer.thread_track("solve") if tracer.enabled else None
    with tracer.span("heat.drive.solve", drv_track, cat="solve",
                     args={"steps": cfg.ntime - start_step, "n": cfg.n,
                           "backend": cfg.backend}):
        return _drive(cfg, T_dev, advance, tracer, drv_track, start_step,
                      to_host, warmup, fetch, warm_exec, two_point_repeats,
                      precompiled, precompile_s)


def _drive(cfg, T_dev, advance, tracer, drv_track, start_step, to_host,
           warmup, fetch, warm_exec, two_point_repeats, precompiled,
           precompile_s) -> SolveResult:
    t_all0 = time.perf_counter()
    chunk = event_interval(cfg)
    remaining = cfg.ntime - start_step
    # computed-against-useful point-steps (runtime/trace.py COUNTERS): the
    # backend reports what its kernels compute for a k-step chunk, if it can
    points_computed = getattr(advance, "points_computed", None)
    computed_by_k: dict = {}
    # and, on a mesh, its halo exchanges, the bytes they send and the
    # exchanges whose column slabs the Pallas strip copy cuts
    halo_counts = getattr(advance, "halo_counts", None)
    halo_by_k: dict = {}

    # AOT-compile every chunk size the loop will encounter (at most two: the
    # steady chunk and a final remainder) so no compile lands inside the
    # timed region and no throwaway compute runs. Analogous to PyCUDA's
    # up-front nvcc JIT (python/cuda/cuda.py:86).
    compile_s = precompile_s
    compiled = dict(precompiled or {})
    if warmup and remaining > 0:
        sizes = chunk_sizes(cfg, remaining)
        with tracer.span("heat.drive.compile", drv_track, cat="solve",
                         args={"sizes": sizes}):
            compiled, spent = aot_compile_chunks(
                advance, T_dev, sizes, compiled,
                label=f"solve {cfg.backend} n{cfg.n}^{cfg.ndim} {cfg.dtype}")
        compile_s += spent
        t0 = time.perf_counter()
        if warm_exec:
            # benchmark mode: one throwaway execution on a copy (donation
            # safety) so first-run runtime initialization — which happens
            # lazily, after .compile() — lands here, not in the timed region
            k0 = min(chunk, remaining)
            sync(compiled[k0](jnp.copy(T_dev)))
        compile_s += time.perf_counter() - t0

    t0 = time.perf_counter()
    step = start_step
    # Async I/O pipeline (the default): checkpoint boundaries cost one
    # on-device buffer copy — the D2H fetch and atomic disk write happen in
    # a bounded-queue background writer while the device keeps stepping —
    # and check_numerics becomes a device-side flag posted at each boundary
    # and fetched at the NEXT one (by which point it computed behind the
    # following chunk). --async-io off restores the reference-shaped
    # sync(T_dev) -> fetch -> save stall below, unchanged.
    async_on = cfg.use_async_io() and bool(cfg.checkpoint_every
                                           or cfg.check_numerics)
    writer = (async_io.SnapshotWriter(tracer=tracer)
              if async_on and cfg.checkpoint_every else None)
    # pending boundary flag from the async numerics leg:
    # (device scalar, step, snapshot-or-None, deferred-checkpoint?)
    pending_flag = None
    # Fault-injection plan (runtime/faults.py): None in every normal run —
    # the loop below then touches nothing fault-related beyond one
    # ``is not None`` test per boundary.
    plan = faults.plan_for(cfg)
    # --on-nan rollback: hold one device snapshot of the newest boundary
    # whose finite flag PASSED; a flagged boundary restores it and re-steps
    # instead of aborting. Deterministic blow-ups re-flag at the same step
    # and abort after _MAX_ROLLBACKS_PER_STEP — only transient faults
    # (soft-error bit flips, injected NaN) actually recover. Costs one
    # device-side copy per boundary, paid ONLY when the mode is on.
    rollback = cfg.on_nan == "rollback" and cfg.check_numerics
    # seed with the starting state so even a first-chunk transient recovers
    last_good = ((async_io.device_snapshot(T_dev), step) if rollback
                 else None)      # (snapshot, step), verified finite
    rollbacks_at: dict = {}      # step -> rollbacks consumed there

    def _submit_snapshot(T_snap, at_step: int) -> None:
        check = cfg.check_numerics
        if tracer.enabled:
            tracer.instant("checkpoint-snapshot", drv_track, cat="solve",
                           args={"step": at_step})

        def job():
            T_ck = to_host(T_snap)  # D2H lands HERE, in the writer thread
            if check:
                # sync mode checks the chunk before saving its boundary;
                # async detects one boundary late (pending_flag), so the
                # writer re-validates the snapshot it is about to persist —
                # a non-finite field never reaches disk on either path
                debug.check_finite(T_ck if T_ck is not None else T_snap,
                                   at_step, label="checkpoint snapshot")
            if T_ck is not None:
                checkpoint.save(cfg, T_ck, at_step)
            else:  # multi-host: each process persists its own shards
                checkpoint.save_shards(cfg, T_snap, at_step)

        job._trace = (f"checkpoint @{at_step}", None)
        writer.submit(job)

    def _try_rollback(bad_step: int) -> bool:
        """Restore the last verified-finite boundary after a flagged one;
        False -> no rollback possible/allowed, the caller re-raises."""
        nonlocal T_dev, step
        if not rollback or last_good is None:
            return False
        n = rollbacks_at.get(bad_step, 0)
        if n >= _MAX_ROLLBACKS_PER_STEP:
            master_print(f"on-nan rollback: step {bad_step} flagged again "
                         f"after {n} rollbacks — deterministic blow-up, "
                         f"aborting")
            return False
        rollbacks_at[bad_step] = n + 1
        snap, good = last_good
        master_print(f"on-nan rollback: non-finite field at step {bad_step}; "
                     f"rolling back to verified boundary {good} "
                     f"(attempt {n + 1}/{_MAX_ROLLBACKS_PER_STEP})")
        # copy the snapshot back in: the restored buffer is donated into the
        # next advance, but last_good must stay restorable for a second try
        T_dev = async_io.device_snapshot(snap)
        step = good
        return True

    def _settle_pending() -> bool:
        """Async mode: judge the boundary flag posted one chunk ago. True ->
        it flagged and we rolled back (caller continues stepping). On a
        pass, promotes the boundary snapshot to last_good and performs its
        deferred checkpoint submit (rollback mode defers persistence until
        the flag verdict so a NaN snapshot never races the writer)."""
        nonlocal pending_flag, last_good
        flag, fstep, snap, is_ckpt = pending_flag
        pending_flag = None
        try:
            debug.raise_if_flagged(flag, fstep)
        except FloatingPointError:
            if _try_rollback(fstep):
                return True
            raise
        if rollback:
            last_good = (snap, fstep)
            if is_ckpt:
                _submit_snapshot(snap, fstep)
        return False

    try:
        with debug.maybe_profile(cfg.profile_dir):
            while True:
                while step < cfg.ntime:
                    k = min(chunk, cfg.ntime - step)
                    fn = compiled.get(k)
                    # dispatch-side span: the enqueue cost, not the device
                    # time (the loop deliberately never fences)
                    with tracer.span("heat.drive.dispatch", drv_track,
                                     cat="solve", args={"k": k}):
                        T_dev = (fn(T_dev) if fn is not None
                                 else advance(T_dev, k))
                    step += k
                    tracer.count("heat.drive.points_useful", cfg.points * k)
                    if points_computed is not None:
                        if k not in computed_by_k:
                            computed_by_k[k] = points_computed(k)
                        if computed_by_k[k] is not None:
                            tracer.count("heat.kernel.points_computed",
                                         computed_by_k[k])
                    if halo_counts is not None:
                        if k not in halo_by_k:
                            halo_by_k[k] = halo_counts(k)
                        exchanges, sent, cuts = halo_by_k[k]
                        tracer.count("heat.halo.exchanges", exchanges)
                        tracer.count("heat.halo.bytes", sent)
                        tracer.count("heat.halo.lane_cuts", cuts)
                    if plan is not None:
                        plan.maybe_crash(step)
                        T_dev = plan.maybe_nan(step, T_dev)
                    if cfg.check_numerics:
                        if async_on:
                            if (pending_flag is not None
                                    and _settle_pending()):
                                continue  # rolled back: re-step the chunk
                            pending_flag = (
                                debug.finite_flag(T_dev), step,
                                async_io.device_snapshot(T_dev)
                                if rollback else None,
                                rollback and writer is not None
                                and cfg.checkpoint_every
                                and step % cfg.checkpoint_every == 0)
                        else:
                            try:
                                debug.check_finite(T_dev, step)
                            except FloatingPointError:
                                if _try_rollback(step):
                                    continue
                                raise
                            if rollback:
                                last_good = (async_io.device_snapshot(T_dev),
                                             step)
                    if cfg.heartbeat_every and step % cfg.heartbeat_every == 0:
                        master_print(" time_it:", step)  # fortran/serial/heat.f90:62
                    if cfg.checkpoint_every and step % cfg.checkpoint_every == 0:
                        if writer is not None:
                            if rollback and async_on:
                                pass  # deferred to _settle_pending: persist
                                      # only flag-verified snapshots
                            else:
                                _submit_snapshot(
                                    async_io.device_snapshot(T_dev), step)
                        else:
                            sync(T_dev)
                            T_ck = to_host(T_dev)
                            if T_ck is not None:
                                checkpoint.save(cfg, T_ck, step)
                            else:
                                checkpoint.save_shards(cfg, T_dev, step)
                if pending_flag is None or not _settle_pending():
                    break
                # final boundary flagged and rolled back: resume stepping
            with tracer.span("heat.drive.sync", drv_track, cat="solve"):
                sync(T_dev)
    except BaseException:
        # drain-on-exception: every queued snapshot still lands on disk (a
        # blow-up's last good boundary is exactly the state a resume
        # needs); a writer error is logged but never masks the solve error
        if writer is not None:
            writer.drain(raise_errors=False)
        raise
    solve_s = time.perf_counter() - t0
    if writer is not None:
        # post-solve flush, deliberately OUTSIDE solve_s: the device has
        # finished stepping, so the remaining writes overlap nothing —
        # they land in io_wait_s and the wall total. Backpressure waits
        # inside the loop above DO sit in solve_s (they stall stepping).
        writer.drain()

    tp_rate = tp_fell_back = None
    if two_point_repeats and remaining > 0:
        k0 = min(chunk, remaining)
        fn = compiled.get(k0) or (lambda t: advance(t, k0))
        # the copy (not T_dev) is donated into the protocol, so the solve's
        # final state survives the extra executions
        tp_res = two_point_rate(fn, jnp.copy(T_dev), cfg.points * k0,
                                repeats=two_point_repeats)
        tp_rate = tp_res[0]
        # surfaced so consumers that must not trust an overhead-dominated
        # rate (calibrate's stencil fits) can refuse it (review r5)
        tp_fell_back = tp_res.fell_back

    # fetch=False skips the final device->host copy (benchmark mode: the
    # caller only wants timings)
    T_host = None
    if fetch:
        with tracer.span("heat.drive.fetch", drv_track, cat="solve"):
            T_host = to_host(T_dev)
    gsum = gsum_dtype = None
    if cfg.report_sum:
        # The intended-but-commented-out global reduction of the reference
        # (mpi+cuda/heat.F90:266-273), done properly. With the field on host,
        # accumulate in f64 so every backend reports the identical sum
        # regardless of storage dtype; without (fetch=False), reduce on
        # device — a scalar fetch — in
        # the widest dtype the platform allows, and LABEL the result
        # (gsum_dtype) so consumers never compare an f32-accumulated sum
        # against the f64 host path at 1e9-cell scale. A multi-host
        # deployment would psum process-local sums instead.
        if T_host is not None:
            gsum = float(np.sum(np.asarray(T_host, np.float64)))
            gsum_dtype = "float64"
        else:
            acc = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            gsum = float(np.asarray(jnp.sum(T_dev, dtype=acc)))
            gsum_dtype = np.dtype(acc).name
    # precompile_s happened before t_all0 (in the caller's guard) — fold it
    # into the wall total as well
    timing = Timing(total_s=time.perf_counter() - t_all0 + precompile_s,
                    compile_s=compile_s,
                    solve_s=solve_s, steps=remaining, points=cfg.points,
                    points_per_s_two_point=tp_rate,
                    two_point_fell_back=tp_fell_back,
                    overlap_s=writer.hidden_s if writer is not None else None,
                    io_wait_s=writer.wait_s if writer is not None else None)
    return SolveResult(cfg=cfg, T=T_host, timing=timing, gsum=gsum,
                       gsum_dtype=gsum_dtype,
                       start_step=start_step, T_dev=T_dev)


def _rebuild_from_shard_blocks(cfg: HeatConfig, sharding, blocks):
    """Reassemble this process's checkpointed blocks into the global sharded
    array (multi-host resume: every process contributes its own blocks)."""
    from ..utils import jnp_dtype

    dt = jnp_dtype(cfg.dtype)
    idx_map = sharding.addressable_devices_indices_map(cfg.shape)
    by_start = {
        tuple(s.start or 0 for s in idx): dev for dev, idx in idx_map.items()
    }
    arrays = []
    for starts, data in blocks:
        dev = by_start.get(tuple(starts))
        if dev is None:
            raise ValueError(
                f"shard checkpoint block at offset {starts} does not match "
                f"the current mesh layout {sorted(by_start)} — resume with "
                f"the mesh shape the checkpoint was written under (or, if "
                f"the shape is unchanged, the shard->device ORDERING moved: "
                f"e.g. a JAX/topology change reordered build_mesh's device "
                f"placement between save and resume)")
        # host->target device in one hop (jnp.asarray would stage through
        # the default device first: a doubled transfer at GiB scale)
        arrays.append(jax.device_put(np.asarray(data).astype(dt), dev))
    return jax.make_array_from_single_device_arrays(cfg.shape, sharding, arrays)


_agree_round = 0  # KV keys must be fresh per agreement (SPMD-aligned calls)


def _allgather_steps(local: int) -> list:
    """Every process's newest shard step, exchanged through the distributed
    coordination service's KV store (gRPC) instead of an XLA collective:
    the CPU backend rejects multiprocess jit programs built outside the
    solve's own shard_map (found by the chaos-launch resume e2e —
    ``multihost_utils.process_allgather`` aborted every restarted world),
    and a 4-byte agreement has no business compiling a program anyway.
    ``blocking_key_value_get`` waits for each peer's key, so no barrier is
    needed; a peer that died pre-publish surfaces as the supervisor seeing
    its corpse, not as a deadlock (the get times out at 120 s)."""
    global _agree_round

    from jax._src.distributed import global_state

    client = getattr(global_state, "client", None)
    if client is None:
        # no coordination service (faked multi-host test seam): the
        # collective fallback — these tests never leave one real process
        from jax.experimental import multihost_utils

        return list(np.asarray(multihost_utils.process_allgather(
            jnp.asarray(local, jnp.int32))))
    _agree_round += 1
    base = f"heat_tpu/resume_step/r{_agree_round}"
    client.key_value_set(f"{base}/{jax.process_index()}", str(local))
    return [int(client.blocking_key_value_get(f"{base}/{i}", 120_000))
            for i in range(jax.process_count())]


def _agree_resume_step(local_step: Optional[int]) -> Optional[int]:
    """Cross-process agreement on the shard-checkpoint resume step.

    Processes can hold different latest steps (a crash between one
    process's save and the others'): resuming at different start_steps
    would desynchronize the collectives. Everyone resumes at the MINIMUM —
    the newest step that every process holds. If any process has no shard
    files at all the minimum is "none": all fall back together (never a
    silent IC start against peers mid-run)."""
    local = -1 if local_step is None else int(local_step)
    if jax.process_count() > 1:
        agreed = int(min(_allgather_steps(local)))
        if agreed != local:
            master_print(f"shard-checkpoint resume: local step {local} vs "
                         f"job-wide agreed step {agreed}")
    else:
        agreed = local
    return None if agreed < 0 else agreed


def resolve_initial_field(cfg: HeatConfig, T0: Optional[np.ndarray],
                          sharding=None):
    """(T_device, start_step) for device backends: explicit T0 > checkpoint
    (both host arrays, shipped over) > IC built directly on device."""
    from ..utils import jnp_dtype

    if (T0 is None and cfg.checkpoint_every and sharding is not None
            and hasattr(sharding, "addressable_devices_indices_map")):
        # multi-host runs checkpoint per-process shard files; prefer them
        # over a (possibly stale) single-host global snapshot
        sstep = _agree_resume_step(
            checkpoint.latest_shards(cfg, max_step=cfg.ntime))
        if sstep is not None:
            gstep = checkpoint.latest_step(cfg, max_step=cfg.ntime)
            if gstep is None or sstep >= gstep:
                blocks, step = checkpoint.load_shards(cfg, sstep)
                T = _rebuild_from_shard_blocks(cfg, sharding, blocks)
                master_print(f"resumed from shard checkpoints at step {step}")
                return T, step

    T0_host, start_step = load_or_init(cfg, T0, default_ic=False)
    if T0_host is None:
        from ..grid import initial_condition_device

        return initial_condition_device(cfg, sharding=sharding), start_step
    T = jnp.asarray(T0_host).astype(jnp_dtype(cfg.dtype))
    T = jax.device_put(T, sharding) if sharding is not None else jax.device_put(T)
    return T, start_step


def load_or_init(cfg: HeatConfig, T0: Optional[np.ndarray], default_ic: bool = True):
    """Resolve the starting field: explicit T0 > latest checkpoint > IC.

    With ``default_ic=False`` the IC fallback returns ``(None, 0)`` instead
    of a host array — device backends then build the IC directly on device
    (grid.initial_condition_device), avoiding the n^d host array and H2D
    transfer entirely.
    """
    from ..grid import initial_condition

    start_step = 0
    if T0 is None and cfg.checkpoint_every:
        ck = checkpoint.latest(cfg, max_step=cfg.ntime)
        if ck is not None:
            T0, start_step = checkpoint.load(ck, cfg)
            master_print(f"resumed from {ck} at step {start_step}")
    if T0 is None:
        if not default_ic:
            return None, 0
        T0 = initial_condition(cfg)
    return np.asarray(T0), start_step
