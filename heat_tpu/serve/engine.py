"""Vmapped simulation lanes: the device half of the serving engine.

One compiled program steps up to ``L`` independent solve requests at once.
The requests of one *bucket* (same ndim/dtype/BC, grid side <= the bucket
side ``B``) are stacked into a single ``(L, B+2, ..., B+2)`` array — each
lane carries its request's field in the ``[1 : 1+n]`` corner of a one-cell-
margined bucket buffer, plus per-lane scalars: the stencil coefficient
``r`` (each request's own ``cfg.r``), the request side ``n``, and the
remaining step count. The chunk program runs ``k`` masked steps under
``lax.fori_loop``: every lane computes the full-bucket stencil every step
(shape-stable — the compiled program never depends on which lanes are
live), and a per-lane/per-cell mask decides what is *kept*:

- cells outside the request region keep their old value, so padding never
  contaminates physics;
- a lane whose ``remaining`` counter has hit zero keeps its whole field,
  so lanes finish at exactly their own step count (step-granular, not
  chunk-granular) and idle until the scheduler swaps them.

Bit-identity with solo runs falls out of the masking scheme, not of luck:

- ``edges`` BC: only request-interior cells update; each reads neighbors
  that are all inside the request region — the same values combined in
  the same left-to-right order as ``ops.stencil.ftcs_step_edges``, and
  float add/mul are elementwise IEEE ops that XLA fusion cannot reorder
  per element. The request's frozen boundary ring blocks every read path
  into the padding.
- ``ghost`` BC: every request cell updates, and the loader establishes
  the invariant that ALL padding cells (the margin ring and the unused
  bucket corner) hold ``bc_value``; the mask never lets them update, so a
  request-edge cell reads exactly the conceptual ``bc_value`` ghost ring
  of ``ops.stencil.ftcs_step_ghost``.
- ``periodic`` BC has no padded-bucket form (wraparound would wrap at the
  bucket edge, not the request edge); the scheduler rejects it per
  request instead of letting the engine mis-serve it.

Compile economics: the stepping program is keyed by (bucket, lane-count,
chunk) — the scheduler fixes lane-count and chunk per engine, so serving
any number of requests costs at most ONE stepping compile per bucket x
lane-count, plus one trivial lane-swap program per bucket (the swap takes
the lane index as a traced scalar precisely so refilling lane 3 vs lane 7
is the same executable). Lane counts are rounded up to power-of-two
*tiers* (``lane_tier``) so waves of 3 and then 5 requests under the same
``--lanes`` cap land on one compiled program instead of two, and a lazily
compiled *tail* program (``chunk // 4`` steps) bounds the masked waste
when every live lane is about to finish — one tail compile per
(bucket, lane-tier), only paid when a tail is actually dispatched.

Dispatch discipline (the PR-4 rework): stepping no longer fences.
``dispatch_chunk`` enqueues one chunk program and returns a *device*
handle to the post-chunk remaining-step vector without any host
round-trip; ``fetch_remaining`` is the only boundary D2H, and the
scheduler calls it on a handle whose chunk was dispatched one or more
chunks ago — the transfer overlaps the chunks queued behind it. The
per-lane scalars (r, side, remaining) are deliberately NOT donated into
the chunk program so an old remaining-handle stays valid while newer
chunks consume the field stack; only the (L, B+2, ...) field buffer —
the allocation that matters — ping-pongs through donation.

Per-lane fault domains (the ISSUE-5 rework): the chunk program
additionally reduces each lane's post-chunk field to a per-lane
``isfinite`` bit and returns an int32 *boundary vector* — row 0 the
remaining-step counts, row 1 the finite bits — so the health verdict
rides the boundary fetch the scheduler already pays for, with no extra
D2H and no change to what the lanes compute (the reduction reads the
fields; it never writes them, so bit-identity is untouched).

Numerics telemetry on the boundary (the ISSUE-15 rework): the boundary
vector is ``(K_BOUNDARY, L)`` = ``(6, L)`` int32 — rows 0–1 the
remaining/finite pair above, unchanged, and rows 2–5 four per-lane
float32 solution-quality statistics BITCAST into the int32 carrier
(``pack_boundary``/``unpack_boundary``): the interior ``max|ΔT|`` over
the chunk's final mini-step (steady-state residual), the
request-region min and max (the discrete-maximum-principle witnesses),
and the total heat content ``ΣT``. Both chunk bodies compute them
fused into the reductions they already run (the XLA body peels the
final ``fori_loop`` step to hold the pre-step stack; the Pallas kernel
accumulates them in the SMEM pass next to the isfinite bit), so
solution-quality telemetry costs zero extra sweeps, zero extra
transfers, and zero change to the field bytes. The stats rows are
always computed (no recompile dimension); ``ServeConfig.numerics``
gates only host-side ingestion (runtime/numerics.py).
``fetch_remaining`` optionally wraps the transfer in a watchdog
(``runtime/async_io.bounded_call``): a wedged device fetch becomes a
clean ``BoundedFetchTimeout`` the scheduler turns into per-request
failures instead of a hung ``heat-tpu serve``.

Pallas-native lane stepping (the ISSUE-9 rework): the chunk program has
two interchangeable bodies — the vmapped masked XLA stencil above (the
bit-exactness ORACLE) and the multi-lane Pallas kernel family
(``ops/pallas_stencil.lane_multistep``): the lane axis becomes a grid
dimension over the solo hand-tuned halo-slab/3x3 plans, with the
per-lane interior mask, the per-lane countdown gate, AND the per-lane
``isfinite`` health reduction fused into the stencil pass itself — lane
health costs zero extra sweeps over the stack. ``resolve_lane_kernel``
maps the ``--serve-lane-kernel auto|pallas|xla`` knob to a backend per
bucket (auto = Pallas on TPU where a kernel plan exists); an
unavailable Pallas program degrades to XLA as a structured
``lane_kernel_fallback`` record + counter, never an error. Rollback
mode additionally drops donation (``donate=False``) so the undonated
input stack of each chunk IS the previous boundary's snapshot — the
old per-chunk full-stack copy program is gone from the dispatch path
entirely (``snapshot_stack``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from ..ops.stencil import accum_dtype_for, laplacian_interior
from ..runtime import trace as trace_mod
from ..utils import jnp_dtype

# BC -> first request-interior offset that updates: ghost updates every
# request cell (offset 0), edges freezes the outermost request ring
# (offset 1). periodic is absent by design (see module docstring).
_BC_LO = {"ghost": 0, "edges": 1}


def host_fetch(x) -> np.ndarray:
    """The ONE device->host fetch seam of the serve hot path.

    Every boundary inspection and lane extraction funnels through here so
    tests can monkeypatch it to prove the dispatch path never fences
    (ISSUE 4 regression contract) and to count fetches per boundary."""
    # heat-tpu: allow[hot-path-purity] THE sanctioned D2H seam itself
    return np.asarray(x)


# The per-lane boundary vector's row layout (ISSUE 15). Rows 0-1 are
# plain int32 (the original remaining/finite pair — every consumer's
# ``rem, finite = b[0], b[1]`` reads them unchanged); rows 2-5 are
# float32 statistics bitcast into the int32 carrier so ONE array — one
# dispatch output, one D2H — carries progress, health, and solution
# quality per lane per chunk.
BOUNDARY_ROWS = ("remaining", "finite", "resid", "tmin", "tmax", "heat")
K_BOUNDARY = len(BOUNDARY_ROWS)


def pack_boundary(remaining, finite, stats):
    """Device-side boundary assembly: stack the int32 remaining/finite
    rows over the ``(4, L)`` float32 stats block bitcast to int32 (a
    free reinterpret — no rounding, NaN/Inf payloads survive exactly).
    The inverse is ``unpack_boundary`` on the fetched host array."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(stats.astype(jnp.float32),
                                        jnp.int32)
    head = jnp.stack([remaining, finite.astype(remaining.dtype)])
    return jnp.concatenate([head, bits], axis=0)


def unpack_boundary(b: np.ndarray) -> np.ndarray:
    """Host-side view of a fetched ``(K_BOUNDARY, L)`` boundary vector's
    stats block: rows 2-5 reinterpreted as float32 — ``(4, L)`` ordered
    (resid, tmin, tmax, heat) per BOUNDARY_ROWS. A bit-level view, not
    a conversion; the int32 head rows are read directly as ``b[0]``,
    ``b[1]`` by every consumer."""
    return np.ascontiguousarray(b[2:K_BOUNDARY]).view(np.float32)


def _lane_stats(prev, fields, n, ndim: int):
    """Per-lane float32 solution-quality stats over the request region.

    The region mask covers buffer coordinates ``[1, n_lane]`` along every
    axis — the full request field INCLUDING its Dirichlet ring (the
    maximum principle bounds interior values by ``[min(IC, bc),
    max(IC, bc)]``, so the witnesses must see the boundary cells), and
    never the padding corner or the margin. Reductions run in float32
    (the bf16 accumulation discipline of ``accum_dtype_for``); they read
    the stacks and write nothing, so field bytes are untouched."""
    import jax
    import jax.numpy as jnp

    lanes = fields.shape[0]
    f32 = fields.astype(jnp.float32)
    mask = None
    for d in range(ndim):
        io = jax.lax.broadcasted_iota(jnp.int32, fields.shape, d + 1)
        nl = n.reshape((lanes,) + (1,) * ndim)
        m = (io >= 1) & (io <= nl)
        mask = m if mask is None else mask & m
    axes = tuple(range(1, ndim + 1))
    delta = jnp.abs(f32 - prev.astype(jnp.float32))
    resid = jnp.max(jnp.where(mask, delta, jnp.float32(0)), axis=axes)
    tmin = jnp.min(jnp.where(mask, f32, jnp.float32(jnp.inf)), axis=axes)
    tmax = jnp.max(jnp.where(mask, f32, jnp.float32(-jnp.inf)), axis=axes)
    heat = jnp.sum(jnp.where(mask, f32, jnp.float32(0)), axis=axes)
    return jnp.stack([resid, tmin, tmax, heat])


def lane_tier(needed: int, cap: int) -> int:
    """Round a wave's lane need up to the next power-of-two tier, capped
    at the configured lane budget. Waves of 3 then 5 requests under
    ``cap=4`` both land on tier 4 — one compiled stepping program where
    ``min(lanes, len(q))`` would have compiled two."""
    if needed < 1 or cap < 1:
        raise ValueError(f"needed/cap must be >= 1, got {needed}/{cap}")
    t = 1
    while t < needed:
        t <<= 1
    return min(cap, t)


# The dimensions of the compiled-stepping-program cache key, in order.
# `heat-tpu audit`'s compile-budget contract reads chunk_cache_key's
# signature and compares it against the budget declared in
# analysis/digests/programs.json — adding a recompile dimension here
# without updating the declared budget fails the audit instead of
# shipping a production compile storm (the PR-4 one-compile-per-combo
# guarantee, made mechanical).
STEP_KEY_DIMS = ("bucket", "lanes", "k", "kernel", "donate")


def chunk_cache_key(bucket: BucketKey, lanes: int, k: int, kernel: str,
                    donate: bool) -> tuple:
    """The ONE cache key under which a compiled lane stepping program is
    stored (LaneEngine._ensure). Every distinct value of this tuple is a
    distinct XLA executable; the audit enumerates this function's image
    over a ServeConfig to bound total compiles."""
    return (bucket, lanes, k, kernel, donate)


def tail_size(chunk: int) -> Optional[int]:
    """Size of the one precompiled tail program per (bucket, lane-tier):
    a quarter chunk (>= 1). When every live lane's remaining count drops
    below ``chunk``, stepping ``ceil(rem / tail)`` tail chunks computes at
    most ``rem + tail - 1`` masked steps instead of a full ``chunk`` —
    bounded waste for one extra (lazily compiled) program. ``None`` for
    chunk 1, where a tail cannot be smaller than the chunk."""
    return chunk // 4 if chunk >= 4 else (1 if chunk > 1 else None)


@dataclasses.dataclass(frozen=True)
class BucketKey:
    """What must match for two requests to share a stacked lane array."""

    ndim: int
    n: int        # bucket side: requests with side <= n fit
    dtype: str
    bc: str

    @property
    def padded_shape(self) -> Tuple[int, ...]:
        """Per-lane buffer shape: bucket side + one-cell margin each side
        (the margin is what lets ``laplacian_interior`` see a neighbor for
        every bucket cell, exactly as the ghost/edges solo paths do)."""
        return (self.n + 2,) * self.ndim


def lane_buffer(key: BucketKey, field: np.ndarray, bc_value: float) -> np.ndarray:
    """Host-side lane image of one request: a bucket buffer filled with
    ``bc_value`` (the ghost-BC invariant; harmless fill for edges) with the
    request field written into the ``[1 : 1+n]`` corner."""
    n = field.shape[0]
    if field.shape != (n,) * key.ndim:
        raise ValueError(f"request field {field.shape} is not square/cubic")
    if n > key.n:
        raise ValueError(f"request side {n} exceeds bucket {key.n}")
    buf = np.full(key.padded_shape, bc_value, dtype=np.float64)
    buf[tuple(slice(1, 1 + n) for _ in range(key.ndim))] = np.asarray(
        field, np.float64)
    return buf


def _lane_step(T, r, n, lo: int):
    """One masked FTCS step of a single lane (vmapped over the lane axis).

    ``T``: the padded bucket buffer; the request occupies interior
    coordinates ``0..n-1`` (buffer ``[1:1+n]``). ``r``/``n`` are this
    lane's scalars. Cells with request-interior coordinate in
    ``[lo, n-1-lo]`` along every axis take the stencil update; everything
    else — the frozen edges ring (lo=1), the padding corner, the margin —
    keeps its old value.
    """
    import jax
    import jax.numpy as jnp

    nd = T.ndim
    acc = accum_dtype_for(T.dtype)
    ctr = tuple(slice(1, -1) for _ in range(nd))
    # identical arithmetic to the solo paths: T + r*lap, summed in the
    # reference's left-to-right order by laplacian_interior
    upd = (T[ctr].astype(acc)
           + r.astype(acc) * laplacian_interior(T)).astype(T.dtype)
    mask = None
    for d in range(nd):
        io = jax.lax.broadcasted_iota(jnp.int32, upd.shape, d)
        m = (io >= lo) & (io <= n - 1 - lo)
        mask = m if mask is None else mask & m
    return T.at[ctr].set(jnp.where(mask, upd, T[ctr]))


def make_lane_advance(key: BucketKey, kernel: str = "xla",
                      donate: bool = True):
    """The jitted chunk program for one bucket: ``advance(fields, r, n,
    remaining, k)`` runs ``k`` masked steps over every lane and returns
    the new state plus the ``(K_BOUNDARY, L)`` boundary vector —
    per-lane remaining steps, ``isfinite`` bits, and the four bitcast
    numerics stats rows (``BOUNDARY_ROWS``), the one array a chunk
    boundary needs to fetch to judge progress, health, AND solution
    quality of every lane.

    ``kernel`` picks the stepping body: ``"xla"`` — the vmapped masked
    stencil under ``lax.fori_loop`` (the serving ORACLE: every other
    backend must match it byte for byte); ``"pallas"`` — the multi-lane
    Pallas kernel family (``ops/pallas_stencil.lane_multistep``: lane
    axis as a grid dimension over the solo halo-slab/3x3 plans, per-lane
    interior mask + countdown gate + isfinite reduction fused into the
    stencil pass, so lane health costs zero extra sweeps). Both bodies
    produce the same remaining-count algebra (``max(rem - k, 0)``) and
    bit-identical fields — gate ``"pallas"`` on ``resolve_lane_kernel``.

    ``donate=True`` donates only the field stack (the buffer that
    matters — it ping-pongs like the solo drive loop's double buffer);
    the per-lane scalars and the boundary vector are left undonated on
    purpose, so a boundary handle taken after chunk ``i`` survives while
    chunks ``i+1..`` are dispatched behind it — the foundation of the
    dispatch-ahead boundary (scheduler.py). ``donate=False`` is rollback
    mode's contract: the undonated input stack IS the previous
    boundary's snapshot, so keeping boundaries restorable costs no
    standalone copy program on the dispatch path (see
    ``LaneEngine.snapshot_stack``)."""
    import jax
    import jax.numpy as jnp

    lo = _BC_LO[key.bc]
    ndim = key.ndim
    donate_argnums = (0,) if donate else ()

    if kernel == "pallas":
        from ..ops.pallas_stencil import lane_multistep

        bucket_n = key.n

        @functools.partial(jax.jit, static_argnums=(4,),
                           donate_argnums=donate_argnums)
        def advance(fields, r, n, remaining, k: int):
            # mask + countdown gate + health reduction + numerics stats
            # all live INSIDE the kernel passes; remaining's update is
            # the same O(L) algebra the fori_loop body produces step by
            # step
            fields, finite, stats = lane_multistep(
                fields, r, n, remaining, k, bc_lo=lo, bucket_n=bucket_n)
            remaining = jnp.maximum(remaining - k, 0)
            boundary = pack_boundary(remaining, finite, stats)
            return fields, r, n, remaining, boundary

        return advance

    step_all = jax.vmap(functools.partial(_lane_step, lo=lo),
                        in_axes=(0, 0, 0))

    @functools.partial(jax.jit, static_argnums=(4,),
                       donate_argnums=donate_argnums)
    def advance(fields, r, n, remaining, k: int):
        def body(_, carry):
            f, rem = carry
            stepped = step_all(f, r, n)
            act = rem > 0
            f = jnp.where(act.reshape(act.shape + (1,) * ndim), stepped, f)
            return f, rem - act.astype(rem.dtype)

        # the final mini-step is peeled out of the loop so the pre-step
        # stack stays in scope for the residual stat — the SAME body,
        # the same per-step elementwise IEEE arithmetic, so the field
        # bytes are untouched (k == 1: the loop is a no-op)
        prev, remaining = jax.lax.fori_loop(0, k - 1, body,
                                            (fields, remaining))
        fields, remaining = body(k - 1, (prev, remaining))
        # per-lane health: one bit per lane, reduced on device — padding
        # cells hold bc_value (finite) and masking confines a NaN to its
        # own lane, so a zero bit is that lane's fault and only its own
        finite = jnp.isfinite(fields).reshape(fields.shape[0], -1).all(axis=1)
        stats = _lane_stats(prev, fields, n, ndim)
        boundary = pack_boundary(remaining, finite, stats)
        return fields, r, n, remaining, boundary

    return advance


def make_lane_loader(key: BucketKey, donate: bool = True):
    """The jitted lane-swap program: replace lane ``lane`` (a TRACED scalar
    — one compile covers every lane index) with a new request's buffer and
    scalars. With ``donate=True`` the field stack is donated like
    ``advance``'s so swapping never copies the other lanes; rollback-mode
    engines pass ``donate=False`` because live boundary snapshots alias
    the stack (donating it would invalidate them — admissions then pay
    one stack copy, chunk dispatch still pays none). The scalar vectors
    are tiny and stay undonated for the same handle-liveness reason."""
    import jax

    donate_argnums = (0,) if donate else ()

    @functools.partial(jax.jit, donate_argnums=donate_argnums)
    def load(fields, r, n, remaining, lane, buf, r_new, n_new, steps_new):
        fields = jax.lax.dynamic_update_index_in_dim(fields, buf, lane, 0)
        return (fields, r.at[lane].set(r_new), n.at[lane].set(n_new),
                remaining.at[lane].set(steps_new))

    return load


def resolve_lane_kernel(requested: str, key: BucketKey):
    """Resolve the ``--serve-lane-kernel`` knob for ONE bucket into the
    backend a lane engine will actually run, plus a fallback reason when
    the resolution is a degradation the operator should hear about.

    Returns ``(kernel, reason)``: ``kernel`` in {"pallas", "xla"};
    ``reason`` is None for a clean resolution and a human string when a
    requested/expected Pallas program is unavailable — the scheduler
    turns that into a structured ``lane_kernel_fallback`` record plus a
    counter, never an error (the XLA lane program is the bit-exact
    oracle; only throughput differs). Rules: ``"xla"`` — always XLA;
    ``"pallas"`` — Pallas when a kernel plan exists for the bucket
    (f64 has none: no TPU VPU f64; nor do 3D buckets whose band fits no
    VMEM plan), loud XLA fallback otherwise; ``"auto"`` — Pallas on TPU
    when a plan exists, XLA elsewhere (off-TPU the Pallas interpreter
    loses to the fused XLA program — that is policy, not a fallback)."""
    if requested == "xla" or key.bc not in _BC_LO:
        return "xla", None
    import jax

    from ..ops.pallas_stencil import lane_kernel_available

    avail = lane_kernel_available(key.ndim, key.n, key.dtype)
    if not avail:
        reason = ("float64 has no Pallas lane kernel (no f64 on the TPU "
                  "VPU)" if key.dtype == "float64" else
                  f"no VMEM-feasible lane band for a {key.ndim}d bucket "
                  f"of side {key.n}")
    if requested == "pallas":
        return ("pallas", None) if avail else ("xla", reason)
    # auto: Pallas exactly where it is the measured win — on TPU
    if jax.default_backend() != "tpu":
        return "xla", None
    return ("pallas", None) if avail else ("xla", reason)


class LaneEngine:
    """Device-side lane state for ONE (bucket, lane-tier) combination.

    The scheduler owns admission, dispatch depth, and swap policy; this
    class owns the arrays and the compiled programs. All methods treat
    the field stack linearly (every stepping/loading call consumes and
    replaces it — the buffer is donated into each jitted program).

    Stepping programs (the steady ``chunk`` and the optional ``tail``)
    compile lazily through ``_ensure`` against a shared ``compiled_cache``
    keyed by (bucket, lane-tier, k); ``on_compile(k, seconds)`` fires for
    every program actually built so the scheduler's compile accounting
    (one stepping compile per combo, plus at most one tail) stays exact.

    ``tracer``/``track`` (runtime/trace.py) take each lane load's
    ``heat.lane.load`` span: the process-global tracer and no ring track
    (profiler only) unless the scheduler passes its own.
    """

    def __init__(self, key: BucketKey, lanes: int, chunk: int,
                 compiled_cache: Optional[Dict] = None,
                 on_compile: Optional[Callable[[int, float], None]] = None,
                 kernel: str = "xla", donate: bool = True,
                 tracer=None, track: Optional[Tuple[int, int]] = None):
        import jax.numpy as jnp

        if key.bc not in _BC_LO:
            raise ValueError(
                f"bc {key.bc!r} has no lane form (periodic wraparound would "
                f"wrap at the bucket edge); supported: {sorted(_BC_LO)}")
        if lanes < 1 or chunk < 1:
            raise ValueError(f"lanes/chunk must be >= 1, got {lanes}/{chunk}")
        if kernel not in ("xla", "pallas"):
            raise ValueError(f"kernel must be 'xla' or 'pallas' (resolve "
                             f"'auto' via resolve_lane_kernel), got "
                             f"{kernel!r}")
        self.key = key
        self.lanes = lanes
        self.chunk = chunk
        self.kernel = kernel
        self.donate = donate
        self.tail = tail_size(chunk)
        if kernel == "pallas":
            from ..ops.pallas_stencil import lane_state_shape

            shape = lane_state_shape(key.ndim, key.n, key.dtype)
            if shape is None:
                raise ValueError(
                    f"no Pallas lane kernel plan for bucket {key} — gate "
                    f"construction on resolve_lane_kernel")
            # the stack lives in the kernel's padded layout for the whole
            # engine lifetime (alignment padding is frozen by the
            # per-lane bounds and never read by a live cell), so chunk
            # dispatch pays zero per-call pad/crop; the request still
            # occupies the [1 : 1+n] corner, so extraction is unchanged
            self._lane_shape = shape
        else:
            self._lane_shape = key.padded_shape
        # points one lane slab holds: what a chunk computes per lane-step
        self.slab_points = math.prod(self._lane_shape)
        self._tracer = tracer if tracer is not None else trace_mod.get_tracer()
        self._track = track
        dt = jnp_dtype(key.dtype)
        acc = accum_dtype_for(dt)
        self._state = (
            jnp.zeros((lanes,) + self._lane_shape, dtype=dt),
            jnp.zeros((lanes,), dtype=acc),          # per-lane r
            jnp.ones((lanes,), dtype=jnp.int32),     # per-lane request side
            jnp.zeros((lanes,), dtype=jnp.int32),    # per-lane steps left
        )
        self._load = make_lane_loader(key, donate=donate)
        self._advance_fn = make_lane_advance(key, kernel=kernel,
                                             donate=donate)
        self._cache = compiled_cache if compiled_cache is not None else {}
        self._on_compile = on_compile
        self.compile_s = 0.0
        # the steady chunk program compiles up front (before any request
        # is admitted into a lane) — the tail program waits for first use
        self._ensure(chunk)

    def _ensure(self, k: int):
        """Compiled executable for a k-step program, built at most once
        per (bucket, lane-tier, k, kernel, donation mode) across the
        scheduler's shared cache (rollback-mode programs donate nothing
        and are distinct executables from the donating default)."""
        ckey = chunk_cache_key(self.key, self.lanes, k, self.kernel,
                               self.donate)
        if ckey not in self._cache:
            from ..backends.common import aot_compile_chunks

            # the compile-observatory key (runtime/prof.py): which lane
            # program this was — bucket geometry x tier x kernel, steady
            # vs tail k — so the structured compile log attributes lazy
            # tail/tier compiles to the group that forced them
            compiled, spent = aot_compile_chunks(
                self._advance_fn, self._state, [k],
                label=(f"lanes {self.key.ndim}d n{self.key.n} "
                       f"{self.key.dtype} {self.key.bc} L{self.lanes}"),
                kernel=self.kernel)
            self._cache[ckey] = compiled[k]
            self.compile_s += spent
            if self._on_compile is not None:
                self._on_compile(k, spent)
        return self._cache[ckey]

    # --- lane I/O ---------------------------------------------------------
    def load_lane(self, lane: int, field: np.ndarray, r: float,
                  steps: int, bc_value: float) -> None:
        """Install one request into ``lane``: pad the host field into a
        bucket buffer and swap it in (one traced-index program).

        The buffer and scalars are converted with NUMPY and handed to the
        jitted loader raw: every ``jnp.asarray``/``jnp.int32`` here would
        be an eager device op — a python-dispatch round trip per argument
        per admission, plus a one-time XLA compile per (shape, dtype) —
        on the serve hot path. The loader's own dispatch does the H2D.
        (numpy handles the bfloat16 cast through ml_dtypes, with the same
        round-to-nearest-even the XLA convert would apply.)"""
        dt = jnp_dtype(self.key.dtype)
        acc = accum_dtype_for(dt)
        with self._tracer.span("heat.lane.load", self._track, cat="lane",
                               args={"lane": lane}):
            buf = lane_buffer(self.key, field, bc_value).astype(dt)
            if buf.shape != self._lane_shape:
                # pallas layout: embed the bucket buffer in the kernel-
                # aligned slab corner; the zero alignment padding is frozen
                # by the per-lane bounds (finite, never read by a live cell)
                slab = np.zeros(self._lane_shape, dtype=dt)
                slab[tuple(slice(0, s) for s in buf.shape)] = buf
                buf = slab
            self._state = self._load(
                *self._state, np.int32(lane), buf,
                np.asarray(r, acc), np.int32(field.shape[0]),
                np.int32(steps))

    def snapshot_lane(self, lane: int):
        """One-lane ON-DEVICE copy of a finished lane (the PR-1 snapshot
        trick, one lane wide): enqueued behind whatever chunks are in
        flight and detached from the donation chain, so stepping resumes
        immediately and the writer thread fetches at its leisure."""
        from ..runtime.async_io import lane_snapshot

        return lane_snapshot(self._state[0], lane)

    def extract(self, snap, n: int) -> np.ndarray:
        """D2H a lane snapshot and crop it to the request's field. This is
        the transfer the dispatch-ahead rework moved OFF the scheduler
        thread — call it from the writer thread."""
        buf = host_fetch(snap)
        return buf[tuple(slice(1, 1 + n) for _ in range(self.key.ndim))]

    def extract_lane(self, lane: int, n: int) -> np.ndarray:
        """Synchronous one-lane fetch (the --dispatch-depth off fallback
        and library spelunking; blocks on every chunk in flight)."""
        return self.extract(self.snapshot_lane(lane), n)

    # --- stepping ---------------------------------------------------------
    def dispatch_chunk(self, k: Optional[int] = None):
        """Enqueue one k-step program (default: the steady chunk) over
        every lane and return a DEVICE handle to the post-chunk
        ``(K_BOUNDARY, L)`` boundary vector (remaining steps, per-lane
        finite bits, bitcast numerics stats) — no host round trip, no
        fence. The handle stays valid under later dispatches because it
        is never donated."""
        fn = self._ensure(self.chunk if k is None else k)
        out = fn(*self._state)
        self._state = out[:4]
        return out[4]

    def fetch_remaining(self, handle, timeout_s: Optional[float] = None,
                        plan=None, fetch_index: int = 0) -> np.ndarray:
        """The boundary D2H: fetch a ``(K_BOUNDARY, L)`` boundary handle
        to host (row 0 remaining steps, row 1 finite bits, rows 2-5 the
        bitcast numerics stats — ``unpack_boundary``). With dispatch depth
        > 1 the scheduler calls this on a chunk dispatched one or more
        chunks ago, so the transfer (and the bookkeeping it gates) hides
        under the chunks queued behind it.

        ``timeout_s`` arms the fetch watchdog: the transfer runs in an
        abandonable thread and a wedged device surfaces as
        ``async_io.BoundedFetchTimeout`` (the scheduler fails that
        group's requests cleanly) instead of hanging the serve loop
        forever. ``plan`` is the active fault plan — the ``fetch-hang``
        injection sleeps INSIDE the watchdogged region, so chaos tests
        exercise the exact production path."""
        return fetch_boundary(handle, timeout_s=timeout_s, plan=plan,
                              fetch_index=fetch_index)

    def step_chunk(self, timeout_s: Optional[float] = None, plan=None,
                   fetch_index: int = 0) -> np.ndarray:
        """Dispatch one steady chunk and immediately fetch its boundary
        vector — the synchronous boundary (``--dispatch-depth off``); the
        fetch doubles as the chunk fence."""
        return self.fetch_remaining(self.dispatch_chunk(),
                                    timeout_s=timeout_s, plan=plan,
                                    fetch_index=fetch_index)

    def remaining(self) -> np.ndarray:
        return np.asarray(self._state[3])

    # --- per-lane fault domains (ISSUE 5) ---------------------------------
    def poison_lane(self, lane: int, n: int) -> None:
        """Chaos-only (``lane-nan`` injection): flip the center cell of
        ``lane``'s request region to NaN. An eager scatter enqueued after
        the chunks already in flight — deterministic in device order, and
        never reached without an active fault plan (hot-path invariant:
        no fault spec, no call)."""
        import jax.numpy as jnp

        idx = (lane,) + tuple(1 + n // 2 for _ in range(self.key.ndim))
        f, r, nn, rem = self._state
        self._state = (f.at[idx].set(jnp.nan), r, nn, rem)

    def perturb_lane(self, lane: int, n: int, eps: float) -> None:
        """Chaos-only (``perturb`` injection, ISSUE 15): add a bounded
        bump ``eps`` to the center cell of ``lane``'s request region —
        finite, so the isfinite bit stays green, but (for any eps above
        the detector tolerance) outside the maximum-principle envelope:
        the numerics observatory's quarry rather than the nonfinite
        path's. Same eager-scatter shape as ``poison_lane``; never
        reached without an active fault plan."""
        import jax.numpy as jnp

        idx = (lane,) + tuple(1 + n // 2 for _ in range(self.key.ndim))
        f, r, nn, rem = self._state
        self._state = (f.at[idx].add(jnp.asarray(eps, f.dtype)), r, nn, rem)

    def snapshot_stack(self):
        """The post-chunk lane stack as a restorable boundary snapshot
        (``--serve-on-nan rollback`` bookkeeping): a lane judged finite
        at that boundary can later be restored from its row.

        Rollback-mode engines are built ``donate=False``, so the live
        stack handle taken here IS a stable snapshot — no later advance
        or load consumes its buffer, and keeping every in-flight
        boundary restorable dispatches NO standalone copy program (the
        pre-rework shape paid one full-stack on-device copy per
        dispatched chunk). At most one buffer stays live per in-flight
        boundary: exactly the advance outputs the pipeline holds anyway.
        A donating engine (where a scheduler never calls this on the
        dispatch path) still gets the defensive on-device copy."""
        if not self.donate:
            return self._state[0]
        from ..runtime.async_io import device_snapshot

        return device_snapshot(self._state[0])

    def restore_lane(self, lane: int, buf, r: float, n: int,
                     steps: int) -> None:
        """Roll ONE lane back to a verified-finite boundary: reuse the
        traced-index loader with an on-device row (no H2D, no new
        compile), resetting the lane's field and its remaining count
        while every other lane is untouched. ``buf`` is not donated, so
        the same snapshot row survives a second rollback attempt."""
        dt = jnp_dtype(self.key.dtype)
        acc = accum_dtype_for(dt)
        self._state = self._load(
            *self._state, np.int32(lane), buf,
            np.asarray(r, acc), np.int32(n), np.int32(steps))


def fetch_boundary(handle, timeout_s: Optional[float] = None, plan=None,
                   fetch_index: int = 0) -> np.ndarray:
    """The ONE watchdogged boundary-D2H path, shared by the packed lane
    engine (``LaneEngine.fetch_remaining``) and the sharded mega-lane
    (``MegaLaneEngine``): fetch a ``(K_BOUNDARY, L)`` boundary handle to
    host, optionally under the ``bounded_call`` watchdog, with the
    ``fetch-hang`` fault injection firing INSIDE the watchdogged region
    either way (runtime/faults.py)."""
    def fetch():
        if plan is not None:
            plan.maybe_fetch_hang(fetch_index)
        # heat-tpu: allow[hot-path-purity] the watchdogged boundary D2H
        return host_fetch(handle)

    if timeout_s is None:
        return fetch()
    from ..runtime.async_io import bounded_call

    return bounded_call(fetch, timeout_s, "serve boundary fetch")


class MegaLaneEngine:
    """Device half of ONE mesh-spanning mega-lane occupant.

    The second placement tier (ISSUE 10): a request that overflows every
    bucket runs as a *sharded mega-lane* — the whole device mesh executes
    the ``backends/sharded.py`` padded-carry chunked advance for that one
    request, wrapped in the exact dispatch contract ``LaneEngine``
    exposes for packed lanes: ``dispatch_chunk(k)`` enqueues one k-step
    program and returns a DEVICE handle to a ``(K_BOUNDARY, 1)``
    boundary vector (remaining steps, an owned-cells ``isfinite`` bit,
    and the bitcast numerics stats reduced over the owned interior) with
    no host round trip; the scheduler's ``fetch_boundary`` is the only D2H; the
    carried padded state is donated through each chunk like the solo
    drive's double buffer. One mega-lane is therefore just a bucket
    group of lane-count one whose "bucket" is the mesh.

    Bit-exactness is inherited, not hoped: the chunk body IS
    ``make_mega_machinery``'s wrap of the solo padded-carry blocks
    (same exchange, same kernel, same bounds), the initial state is the
    same device-built IC + seed the solo path resolves, and owned-cell
    values are invariant under chunk partitioning (the fused-exchange
    margin argument) — so serving in ``--chunk``-step slices produces
    the byte-identical field a solo ``drive()`` of the same config
    yields in one call.

    Compile economics: the seed/crop programs and every chunk size this
    occupant will run (the steady ``chunk`` plus at most one remainder)
    are AOT-compiled at admission through the engine-shared cache, keyed
    by (config geometry, mesh, k) — re-admitting the same oversized
    config costs zero compiles, and nothing ever compiles inside the
    dispatch loop."""

    def __init__(self, cfg, mesh, chunk: int,
                 compiled_cache: Optional[Dict] = None,
                 on_compile: Optional[Callable[[int, float], None]] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.chunk = chunk
        self._cache = compiled_cache if compiled_cache is not None else {}
        self._on_compile = on_compile
        # geometry + physics fields that select a distinct compiled
        # program family (r folds in sigma/nu/dom_len/n; comm and
        # local_kernel shape the shard body)
        self._ckey = ("mega", cfg.ndim, cfg.n, cfg.dtype, cfg.bc,
                      repr(cfg.bc_value), repr(float(cfg.r)),
                      tuple(mesh.devices.shape), cfg.comm,
                      cfg.local_kernel, cfg.fuse_steps)
        self._label = (f"mega {cfg.ndim}d n{cfg.n} {cfg.dtype} {cfg.bc} "
                       f"mesh {'x'.join(map(str, mesh.devices.shape))}")
        m = self._machinery()
        self.kf = m["kf"]
        self._advance = m["advance"]
        self._seed_c = m["seed"]
        self._crop_c = m["crop"]
        for k in self.chunk_sizes():
            self._ensure(k)
        self.reload()

    # --- compiled-program plumbing ----------------------------------------
    def _structs(self, kf: int):
        """(owned, padded) ShapeDtypeStructs the seed/crop programs
        compile against — the same derivation the sharded compile guard
        uses (``_probe_state_struct``)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..backends.sharded import _probe_state_struct

        cfg, mesh = self.cfg, self.mesh
        sharding = NamedSharding(mesh, P(*mesh.axis_names))
        owned = jax.ShapeDtypeStruct(cfg.shape, jnp_dtype(cfg.dtype),
                                     sharding=sharding)
        return owned, _probe_state_struct(cfg, mesh, kf, padded=True)

    def _machinery(self) -> dict:
        """Build (or fetch warm) the mega machinery for this (config,
        mesh): the jitted advance plus AOT-compiled seed/crop programs.
        Cached engine-wide so a second admission of the same oversized
        config compiles nothing."""
        key = ("mega-mach",) + self._ckey
        m = self._cache.get(key)
        if m is None:
            from ..backends.sharded import make_mega_machinery
            from ..runtime import prof

            t0 = time.perf_counter()
            seed, advance, crop, kf = make_mega_machinery(self.cfg,
                                                          self.mesh)
            owned, padded = self._structs(kf)
            m = {"kf": kf, "advance": advance,
                 "seed": seed.lower(owned).compile(),
                 "crop": crop.lower(padded).compile()}
            spent = time.perf_counter() - t0
            self._cache[key] = m
            prof.compile_log().note(self._label + " seed/crop", 0, spent)
            if self._on_compile is not None:
                self._on_compile(0, spent)
        return m

    def chunk_sizes(self) -> list:
        """Every k the occupant's drain will dispatch: the steady chunk
        plus at most one remainder (the solo drive's chunk_sizes shape,
        with the serve chunk as the event interval)."""
        ntime = self.cfg.ntime
        if ntime <= 0:
            return []
        k0 = min(self.chunk, ntime)
        sizes = {k0}
        if ntime % k0:
            sizes.add(ntime % k0)
        return sorted(sizes)

    def _ensure(self, k: int):
        ckey = self._ckey + (k,)
        if ckey not in self._cache:
            from ..backends.common import aot_compile_chunks

            import jax

            _, padded = self._structs(self.kf)
            rem = jax.ShapeDtypeStruct((1,), np.int32)
            compiled, spent = aot_compile_chunks(
                self._advance, (padded, rem), [k], label=self._label,
                kernel="sharded")
            self._cache[ckey] = compiled[k]
            if self._on_compile is not None:
                self._on_compile(k, spent)
        return self._cache[ckey]

    # --- state lifecycle --------------------------------------------------
    def reload(self) -> None:
        """(Re)build the carried padded state from the deterministic
        initial condition — admission, and the rollback path's
        no-verified-boundary-yet restart. The IC is the device-built,
        mesh-sharded construction the solo sharded drive resolves, so
        the starting bytes match a solo run's exactly."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..grid import initial_condition_device

        sharding = NamedSharding(self.mesh, P(*self.mesh.axis_names))
        T0 = initial_condition_device(self.cfg, sharding=sharding)
        self._state = self._seed_c(T0)
        del T0
        self._rem = np.asarray([self.cfg.ntime], np.int32)

    def load(self, T, steps_left: int) -> None:
        """Seed the carried padded state from a HOST field with
        ``steps_left`` steps to go — engine-state resume (serve
        --resume). Owned-cell values are invariant under chunk
        partitioning (the fused-exchange margin argument the solo
        sharded drive rides), so seeding from a cropped checkpoint
        field at a chunk boundary continues bit-identically."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.mesh, P(*self.mesh.axis_names))
        T_dev = jax.device_put(
            np.asarray(T, dtype=jnp_dtype(self.cfg.dtype)), sharding)
        self._state = self._seed_c(T_dev)
        del T_dev
        self._rem = np.asarray([int(steps_left)], np.int32)

    def dispatch_chunk(self, k: int):
        """Enqueue one k-step mesh program and return the DEVICE handle
        to its ``(K_BOUNDARY, 1)`` boundary vector — no fence, no host
        round trip (the mega mirror of ``LaneEngine.dispatch_chunk``)."""
        fn = self._ensure(k)
        self._state, self._rem, boundary = fn(self._state, self._rem)
        return boundary

    def snapshot_state(self):
        """Restorable on-device copy of the carried state (rollback
        bookkeeping). The mega state IS donated through each chunk (the
        whole point of padded-carry), so unlike the packed lanes'
        aliasing trick this pays one device-side copy per dispatched
        chunk — only in rollback mode, the PR-5 pre-rework shape."""
        from ..runtime.async_io import device_snapshot

        return device_snapshot(self._state)

    def restore(self, snap, steps_left: int) -> None:
        """Roll the mega-lane back to a verified-finite boundary. The
        snapshot is copied in (not adopted): a second rollback attempt
        must find it intact."""
        from ..runtime.async_io import device_snapshot

        self._state = device_snapshot(snap)
        self._rem = np.asarray([steps_left], np.int32)

    def final_snapshot(self):
        """Crop the padded carried state to the owned global field — a
        device program enqueued behind whatever is in flight; the D2H
        happens in the writer thread via ``extract``."""
        return self._crop_c(self._state)

    @staticmethod
    def extract(snap) -> np.ndarray:
        """D2H a cropped final field (writer thread). Static on purpose:
        the writeback closure must not pin the multi-shard padded state
        alive, only the cropped snapshot."""
        return host_fetch(snap)

    def _center_index(self) -> tuple:
        """Global index of the centre owned cell in the carried state,
        where each shard takes ``carried_shapes``' carried extent: the
        logical padded shard at its origin, alignment cells beyond."""
        from ..backends.sharded import carried_shapes

        sizes = self.mesh.devices.shape
        carried = carried_shapes(self.cfg, sizes, self.kf)[1]
        idx = []
        for s, c in zip(sizes, carried):
            shard, off = divmod(self.cfg.n // 2, self.cfg.n // int(s))
            idx.append(shard * c + self.kf + off)
        return tuple(idx)

    def poison_center(self) -> None:
        """Chaos-only (``lane-nan`` injection on a mega request): NaN the
        center OWNED cell of the carried padded state. Device placement
        is re-pinned to the state's sharding so the compiled advance's
        input layout contract survives the eager scatter."""
        import jax
        import jax.numpy as jnp

        poisoned = self._state.at[self._center_index()].set(jnp.nan)
        self._state = jax.device_put(poisoned, self._state.sharding)

    def perturb_center(self, eps: float) -> None:
        """Chaos-only (``perturb`` injection on a mega request, ISSUE 15):
        add a bounded bump to the center owned cell — finite (the isfinite
        bit stays green) but outside the maximum-principle envelope for
        any eps above the detector tolerance. Same placement re-pin as
        ``poison_center``."""
        import jax
        import jax.numpy as jnp

        bumped = self._state.at[self._center_index()].add(
            jnp.asarray(eps, self._state.dtype))
        self._state = jax.device_put(bumped, self._state.sharding)


def wall_clock() -> float:
    """Seam for tests; the scheduler stamps queue/serve waits with this."""
    return time.perf_counter()


# --- program-registry seam (ISSUE 13) ----------------------------------------
# Every program family the lane/mega engines compile, as abstract
# ProgramSpecs: `heat-tpu audit` traces and lowers them on shape structs
# (no engine, no device state, no execution) to machine-check donation,
# purity, dtype discipline, and digest drift. Keep this list in lockstep
# with what the engines actually build — a family missing here is a
# family the audit cannot see.

def _lane_structs(key: BucketKey, lanes: int, kernel: str = "xla"):
    """Abstract (fields, r, n, remaining) argument structs for one lane
    engine's programs — the exact shapes/dtypes LaneEngine.__init__
    allocates, including the Pallas kernel's padded slab layout."""
    import jax

    dt = jnp_dtype(key.dtype)
    acc = accum_dtype_for(dt)
    if kernel == "pallas":
        from ..ops.pallas_stencil import lane_state_shape

        shape = lane_state_shape(key.ndim, key.n, key.dtype)
    else:
        shape = key.padded_shape
    return (jax.ShapeDtypeStruct((lanes,) + shape, dt),
            jax.ShapeDtypeStruct((lanes,), acc),
            jax.ShapeDtypeStruct((lanes,), np.int32),
            jax.ShapeDtypeStruct((lanes,), np.int32))


def lane_program_specs():
    """Every packed-lane program family (stepping XLA/Pallas, rollback,
    tail, loader) at a representative bucket — small enough to trace in
    seconds, wide enough that each contract family has a real subject."""
    from ..analysis.programs import ProgramSpec
    from ..ops.pallas_stencil import lane_kernel_available

    B, L, K = 64, 4, 8

    def _advance_build(key, kernel, donate, k):
        def build():
            adv = make_lane_advance(key, kernel=kernel, donate=donate)
            return adv, _lane_structs(key, L, kernel) + (k,), (4,)
        return build

    def _loader_build(key):
        def build():
            import jax

            dt = jnp_dtype(key.dtype)
            acc = accum_dtype_for(dt)
            load = make_lane_loader(key, donate=True)
            args = _lane_structs(key, L) + (
                jax.ShapeDtypeStruct((), np.int32),
                jax.ShapeDtypeStruct(key.padded_shape, dt),
                jax.ShapeDtypeStruct((), acc),
                jax.ShapeDtypeStruct((), np.int32),
                jax.ShapeDtypeStruct((), np.int32))
            return load, args, ()
        return build

    def _spec(dtype, bc, kernel="xla", donate=True, k=K, tag=""):
        key = BucketKey(2, B, dtype, bc)
        name = f"lane/{kernel}/2d/n{B}/{dtype}/{bc}{tag}"
        return ProgramSpec(
            name=name, build=_advance_build(key, kernel, donate, k),
            donated=(0,) if donate else (), no_alias=not donate,
            dtype=dtype, storage_round=(dtype == "bfloat16"), steps=k,
            lanes=L, kernel=kernel, family="lane",
            bucket=f"2d/n{B}/{dtype}/{bc}")

    specs = [
        _spec("float32", "edges"),
        # rollback mode: the undonated input stack IS the boundary
        # snapshot (PR 9) — the audit proves it never aliases an output
        _spec("float32", "edges", donate=False, tag="/rollback"),
        _spec("float32", "edges", k=tail_size(16), tag="/tail"),
        _spec("bfloat16", "edges"),
    ]
    for dtype in ("float32", "bfloat16"):
        if lane_kernel_available(2, B, dtype):
            specs.append(_spec(dtype, "edges", kernel="pallas"))
    key3 = BucketKey(3, 16, "float32", "ghost")
    specs.append(ProgramSpec(
        name="lane/xla/3d/n16/float32/ghost",
        build=_advance_build(key3, "xla", True, K), donated=(0,),
        dtype="float32", steps=K, lanes=L, kernel="xla", family="lane",
        bucket="3d/n16/float32/ghost"))
    key = BucketKey(2, B, "float32", "edges")
    specs.append(ProgramSpec(
        name=f"lane/load/2d/n{B}/float32/edges", build=_loader_build(key),
        donated=(0,), dtype="float32", steps=0, lanes=L, kernel="xla",
        family="loader"))
    return specs


def mega_program_specs():
    """The sharded mega-lane chunk program (ISSUE 10) on a 1x1 mesh —
    mesh-shape-pinned so the digest is stable on any host; real meshes
    change shard counts, not the contract set."""
    from ..analysis.programs import ProgramSpec
    from ..config import HeatConfig

    def build():
        import jax

        from ..backends.sharded import (_probe_state_struct,
                                        make_mega_machinery)
        from ..parallel.mesh import build_mesh

        cfg = HeatConfig(n=32, ndim=2, dtype="float32", bc="ghost",
                         ntime=16, backend="sharded", mesh_shape=(1, 1))
        mesh = build_mesh(cfg.ndim, cfg.mesh_shape)
        _, advance, _, kf = make_mega_machinery(cfg, mesh)
        padded = _probe_state_struct(cfg, mesh, kf, padded=True)
        rem = jax.ShapeDtypeStruct((1,), np.int32)
        return advance, (padded, rem, 8), (2,)

    return [ProgramSpec(
        name="mega/sharded/2d/n32/float32/ghost", build=build,
        donated=(0,), dtype="float32", steps=8, lanes=1,
        kernel="sharded", family="mega")]
