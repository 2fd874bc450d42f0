"""Admission queue + shape bucketing + dispatch-ahead continuous batching.

The serving contract, in the shape of an inference server's scheduler:

- **Admission**: ``Engine.submit(cfg)`` validates a request against the
  bucket table and enqueues it. A request the engine cannot serve (side
  larger than the biggest bucket; periodic BC, which has no padded-lane
  form) is *rejected as a record*, never as an engine error — multi-tenant
  serving must not let one bad request take down the queue.
- **Bucketing**: requests are grouped by ``BucketKey`` (ndim, smallest
  bucket side that fits, dtype, BC). One group = one stacked lane array =
  at most one stepping-program compile per (bucket, lane-tier) no matter
  how many requests flow through it — lane counts round UP to power-of-two
  tiers (``engine.lane_tier``) so uneven waves share programs.
- **Continuous batching, dispatch-ahead**: the chunk loop never stops for
  a single lane, and (the PR-4 rework) the device never waits on the host
  between chunks. The scheduler keeps ``dispatch_depth`` chunk programs in
  flight per group and inspects the remaining-step vector of the OLDEST
  one — fetched while the newer chunks compute behind it, so the
  boundary's D2H and python bookkeeping overlap device work instead of
  fencing it. Finished lanes take a one-lane on-device snapshot
  (``runtime/async_io.lane_snapshot``) and stepping resumes immediately;
  the D2H + result write happen wholly in the ``SnapshotWriter`` thread.
  ``Engine.run`` round-robins chunk dispatch across all live bucket
  groups, so one group's boundary bookkeeping hides under another group's
  compute. ``dispatch_depth=0`` is the fully synchronous debugging
  fallback (fetch-every-boundary, extraction on the scheduler thread —
  the PR-3 shape).
- **Determinism of the boundary**: the device decrements each lane's
  remaining count by exactly one per step while positive, so the host
  mirrors the countdown and PREDICTS every chunk's post-step vector at
  dispatch time. Prediction drives dispatch policy (is another chunk
  useful; steady chunk vs tail); the fetched vector stays the ground
  truth for finishing lanes — and must equal the prediction, enforced
  per boundary (a divergence means the masking contract broke, and a
  serving engine must never silently mis-serve). Lanes whose occupant
  was swapped in after a chunk was dispatched are guarded by a per-lane
  epoch: a stale in-flight chunk cannot "finish" the new occupant.
- **Tail chunks**: when every live lane's remaining count has dropped
  below the chunk (and far enough that it saves compute), the group
  dispatches a lazily-precompiled quarter-chunk tail program instead of
  a mostly-masked full chunk — at most ONE extra compile per
  (bucket, lane-tier), waste bounded by the tail size.
- **Fault isolation**: an injected or real sink failure on one request's
  writeback (``sink-error`` in runtime/faults.py grammar) fails THAT
  request's record; transient errors still ride the writer's bounded
  in-thread retry, and the engine keeps draining the other lanes either
  way.
- **Per-lane fault domains** (ISSUE 5): every chunk boundary carries a
  per-lane ``isfinite`` bit next to the remaining-step vector (computed
  on device, fetched in the boundary D2H the scheduler already pays —
  serve/engine.py). A flagged lane is **quarantined**: its record fails
  with a structured ``nonfinite`` status and approximate step, the lane
  is freed for the admission queue, and every other lane continues
  bit-identically (the masking contract confines a NaN to its own lane).
  ``--serve-on-nan rollback`` instead mirrors ``drive()``'s per-solve
  contract per lane: each dispatched chunk keeps an on-device snapshot
  of its post-chunk stack, a lane judged finite at a boundary promotes
  that snapshot row to its last-good state, and a flagged lane is
  restored and re-stepped alone — transient poison recovers
  bit-identically, a deterministic blow-up re-flags and is quarantined
  after a bounded retry budget. Requests may carry a ``deadline_ms``
  (engine default ``--serve-deadline``); an over-deadline lane is
  preempted at its next boundary with status ``deadline`` and still-
  queued requests past their deadline are shed without ever occupying a
  lane. ``--max-queue`` bounds admission (excess requests get a
  structured ``overloaded`` rejection instead of an unbounded queue),
  and the boundary fetch runs under a watchdog (``--fetch-watchdog``):
  a wedged device fetch fails that group's in-flight and queued
  requests cleanly instead of hanging ``heat-tpu serve`` forever.
  Freed-but-unreplaced lanes keep counting down on device (masked,
  garbage-stepping at worst) so the host countdown mirror — and the
  desync cross-check — stay exact without an extra device program.

- **Lane-kernel selection** (ISSUE 9): each bucket group resolves
  ``ServeConfig.lane_kernel`` (``--serve-lane-kernel auto|pallas|xla``)
  through ``engine.resolve_lane_kernel`` — the multi-lane Pallas kernels
  where available (auto: on TPU), the vmapped XLA oracle elsewhere. A
  requested-but-unavailable Pallas bucket degrades to XLA as a
  per-(bucket, tier) structured ``lane_kernel_fallback`` record +
  counter + /metrics gauge, never an error. Rollback mode additionally
  builds its engines ``donate=False``: each dispatched chunk's
  undonated input stack IS the previous boundary's snapshot, so
  keeping boundaries restorable costs no standalone copy program on
  the dispatch path.

- **Two-tier placement** (ISSUE 10): a request whose side overflows
  every bucket no longer dies as a ``bucket-overflow`` rejection — on a
  multi-device host it is admitted to the engine-wide mega queue and
  runs as a **sharded mega-lane**: one request occupying the whole
  device mesh via the ``backends/sharded.py`` padded-carry chunked
  advance (``MegaLaneRunner`` + ``serve/engine.py MegaLaneEngine``),
  under the same dispatch-ahead contract as the packed lanes (boundary
  handle, dispatch depth, countdown mirror, isfinite bit, deadline /
  quarantine / rollback / watchdog — one mega-lane is a fault domain of
  size one-mesh). ``Engine.run``'s round-robin treats mega slots as
  just more groups, so packed traffic and a resident mega-lane hide
  each other's boundary bookkeeping. ``--mega-lanes N`` gates the tier
  (auto: 1 on a multi-device mesh, 0 single-device where overflow stays
  a rejection — bit-identical to the pre-mega engine); every record,
  cost-model row, /metrics gauge, usage stamp, and trace row carries a
  ``placement=packed|mega`` dimension.

Per-request structured JSON records (queue wait, steps/s, lane id) go
through ``runtime/logging``; each request also keeps a python-level record
for library callers (``Engine.results()``). Records are mutated from both
the scheduler thread and the writer thread — one engine-wide lock guards
every record mutation and every ``json_record`` emission so JSON lines
cannot interleave mid-line.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable, Dict, List, Optional

import numpy as np

from ..config import (DEFAULT_SLO_CLASS, DEFAULT_TENANT, LANE_KERNELS,
                      SLO_TARGETS, HeatConfig, validate_slo_fields,
                      validate_until_fields)
from ..grid import ic_envelope, initial_condition
from ..runtime import async_io, faults
from ..runtime import checkpoint as ckpt_mod
from ..runtime import convergence as conv_mod
from ..runtime import debug as debug_mod
from ..runtime import numerics as numerics_mod
from ..runtime import prof as prof_mod
from ..runtime import trace as trace_mod
from ..runtime.logging import json_record, master_print
from . import policy as policy_mod
from . import solvecache as solvecache_mod
from .engine import (BucketKey, LaneEngine, MegaLaneEngine, lane_tier,
                     resolve_lane_kernel, unpack_boundary, wall_clock)
from .engine import fetch_boundary as engine_fetch_boundary

# Statuses a record can never leave: what poll()/wait() callers and the
# gateway's streaming responses key on.
TERMINAL_STATUSES = ("ok", "rejected", "error", "nonfinite", "deadline")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine-level knobs (the per-request physics lives in HeatConfig)."""

    lanes: int = 4            # max concurrent requests per bucket group
                              # (waves round up to power-of-two tiers
                              # capped here — see engine.lane_tier)
    chunk: int = 16           # steps per device program call (the swap
                              # granularity of continuous batching)
    buckets: tuple = (256, 512, 1024)  # grid-side buckets; a request is
                              # padded up to the smallest side that fits
    dispatch_depth: int = 2   # chunk programs kept in flight per group
                              # before the scheduler blocks on a boundary
                              # fetch; 1 = fetch the chunk just dispatched
                              # (pipelined bookkeeping only), 0 = fully
                              # synchronous fallback for debugging (the
                              # PR-3 fence-every-chunk shape, extraction
                              # on the scheduler thread)
    out_dir: Optional[str] = None  # writeback directory (<id>.npz); None =
                              # results kept in-memory on the records
    keep_fields: bool = False  # keep final fields on records even when
                              # writing files (tests / library callers)
    emit_records: bool = True  # print one JSON line per finished request
    on_nan: str = "fail"      # a lane whose boundary finite bit drops:
                              # "fail" quarantines the request (structured
                              # nonfinite record, lane freed); "rollback"
                              # restores the lane's last verified-finite
                              # boundary snapshot and re-steps only that
                              # lane (bounded retries — deterministic
                              # blow-ups still quarantine)
    deadline_ms: Optional[float] = None  # engine-default per-request wall
                              # budget from submit; a request's own
                              # deadline_ms overrides. Over-deadline lanes
                              # preempt at their next chunk boundary
                              # (status "deadline"); None = no deadline
    max_queue: Optional[int] = None  # admission bound: submits beyond this
                              # many queued requests are shed with a
                              # structured "overloaded" rejection;
                              # None/0 = unbounded
    fetch_timeout_s: Optional[float] = 600.0  # boundary-fetch watchdog: a
                              # boundary D2H exceeding this fails the
                              # group's requests cleanly instead of
                              # hanging the serve loop (None = off; the
                              # default mirrors the writer drain bound)
    inject: str = ""          # engine-scoped fault spec (runtime/faults.py
                              # grammar incl. the serve kinds lane-nan /
                              # fetch-hang); per-request specs ride each
                              # request's own "inject" key
    policy: str = "fifo"      # admission ordering (serve/policy.py):
                              # "fifo" = submit order (bit-identical to
                              # the pre-policy engine), "edf" = SLO-class
                              # priority + earliest-deadline-first within
                              # a class, "fair" = weighted fair share
                              # across tenants with EDF inside each
    tenant_weights: tuple = ()  # (("name", weight), ...) fair-share
                              # weights; unlisted tenants weigh 1.0
    tenant_quota: Optional[int] = None  # per-tenant admission sub-quota:
                              # one tenant may hold at most this many
                              # queued requests (structured "overloaded"
                              # rejection past it) — the flood guard
                              # --max-queue alone cannot give, because a
                              # single tenant can fill a shared bound;
                              # None/0 = no per-tenant bound
    trace: Optional[str] = None  # export the run's event ring as Chrome
                              # trace-event JSON here at drain (Perfetto /
                              # chrome://tracing); None = flight-recorder
                              # only (ring retained, dumped on faults)
    trace_buffer: int = trace_mod.DEFAULT_BUFFER  # event-ring capacity
                              # (runtime/trace.py); 0 disables recording
                              # entirely — including the flight recorder
    flight_dir: Optional[str] = None  # flight-recorder dump directory
                              # (flightrec-<ts>.trace.json on watchdog /
                              # quarantine-after-rollbacks / scheduler
                              # crash); None = out_dir. With neither set
                              # the dump is skipped (never the cwd — the
                              # ring is retained in memory either way)
    prof: bool = True         # the performance & cost observatory
                              # (runtime/prof.py): online chunk-cost
                              # model, per-tenant usage ledger, memory
                              # watermarks, SLO burn-rate monitor — fed
                              # from timestamps the scheduler already
                              # takes. off = aggregation/model/sampling
                              # disabled (records keep their usage
                              # stamps so the schema never flickers);
                              # the A/B baseline of
                              # benchmarks/prof_overhead_lab.py
    slo_targets: tuple = ()   # (("class", target), ...) per-class SLO
                              # target overrides (deadline-hit fraction;
                              # defaults config.SLO_TARGETS) — the burn
                              # monitor's error budget is 1 - target
    slo_burn_threshold: float = prof_mod.SLO_BURN_THRESHOLD
                              # emit a structured slo_alert when a
                              # class's FAST and SLOW windows both burn
                              # budget above this multiple of the
                              # sustainable rate
    slo_fast_window_s: float = prof_mod.SLO_FAST_WINDOW_S
    slo_slow_window_s: float = prof_mod.SLO_SLOW_WINDOW_S
    mem_poll_every: int = prof_mod.MEM_POLL_EVERY_DEFAULT
                              # chunk boundaries between device-memory
                              # watermark samples (leak sentinel);
                              # 0 = never sample
    mega_lanes: Optional[int] = None  # second placement tier (ISSUE 10):
                              # how many mesh-spanning sharded mega-lanes
                              # may run concurrently. A request whose side
                              # overflows every bucket is admitted to the
                              # mega queue instead of rejected and runs as
                              # ONE request occupying the whole device
                              # mesh (backends/sharded.py chunked advance
                              # under the same dispatch-ahead contract).
                              # None = auto: 1 when the host has > 1
                              # device, 0 on single-device hosts where
                              # overflow stays a rejection; 0 restores
                              # the pre-mega behavior bit-identically
    lane_kernel: str = "auto"  # chunk-program body per bucket
                              # (--serve-lane-kernel): "auto" = the
                              # multi-lane Pallas kernels on TPU wherever
                              # the bucket has a kernel plan, the vmapped
                              # XLA stencil elsewhere; "pallas"/"xla"
                              # force it. An unavailable Pallas bucket is
                              # a per-(bucket, tier) structured
                              # lane_kernel_fallback record + counter,
                              # never an error; the XLA program stays the
                              # bit-exactness oracle (engine.py
                              # resolve_lane_kernel)
    numerics: bool = True     # the numerics observatory (runtime/
                              # numerics.py, ISSUE 15): per-lane solution-
                              # quality detectors fed from the stats rows
                              # the chunk programs ALWAYS fuse into the
                              # boundary vector. off = host-side ingestion
                              # disabled only — the device programs are
                              # identical either way, so results stay
                              # byte-identical on vs off (the A/B of
                              # benchmarks/numerics_overhead_lab.py)
    steady_tol: float = 1e-12  # steady-state detector (--steady-tol): a
                              # lane whose final-mini-step residual EWMA
                              # sits below this while steps remain emits
                              # ONE steady_state record per request; for
                              # until=steady requests (per-request "tol"
                              # overrides this default) the scheduler
                              # also ACTS on it — the lane retires at its
                              # dispatch frontier with exit=steady
                              # (semantic scheduling, ISSUE 16)
    numerics_guard: str = "warn"  # violation routing (--numerics-guard):
                              # "warn" = structured numerics_violation
                              # record + flight dump only; "quarantine" =
                              # additionally take the PR-5 quarantine
                              # exit — the request fails nonfinite, the
                              # lane frees, co-scheduled lanes continue
                              # byte-identically
    engine_ckpt_interval: int = 0  # zero-downtime serving (ISSUE 17):
                              # checkpoint the whole engine state (lane
                              # fields + occupancy/queue/usage manifest)
                              # every N processed chunk boundaries, and
                              # always at drain; ``serve --resume DIR``
                              # reconstructs the engine from the newest
                              # valid generation. 0 = off (no manifest is
                              # ever written — bit-identical to PR 16)
    engine_ckpt_dir: Optional[str] = None  # manifest + lane-field
                              # directory; None = <out_dir>/engine-ckpt,
                              # or ./engine-ckpt with no out_dir
    cache: bool = False       # two-level solve cache (ISSUE 19): consult
                              # the content-addressed result store at
                              # submit — a full hit replays the stored
                              # npz byte-identically without occupying a
                              # lane (billed usage.cached, zero
                              # lane_s/steps); a prefix hit seeds the
                              # lane from the deepest shallower entry
                              # and steps only the delta — and publish
                              # every ok result + chunk-boundary lane
                              # snapshot back into it. Off (default) is
                              # bit-identical to pre-cache behavior:
                              # no directory is ever touched
    cache_dir: Optional[str] = None  # entry directory (shared across a
                              # fleet on shared storage); None =
                              # <out_dir>/solve-cache, or ./solve-cache
                              # with no out_dir
    cache_max_bytes: int = 0  # LRU-evict oldest entries once total
                              # entry bytes exceed this (0 = unbounded)

    def __post_init__(self):
        if self.lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {self.lanes}")
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        if self.dispatch_depth < 0:
            raise ValueError(f"dispatch_depth must be >= 0 (0 = sync "
                             f"fallback), got {self.dispatch_depth}")
        if not self.buckets or any(b < 3 for b in self.buckets):
            raise ValueError(f"buckets must be sides >= 3, got {self.buckets}")
        if self.on_nan not in ("fail", "rollback"):
            raise ValueError(f"on_nan must be 'fail' or 'rollback', "
                             f"got {self.on_nan!r}")
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0 (None = no "
                             f"deadline), got {self.deadline_ms}")
        if self.max_queue is not None and self.max_queue < 0:
            raise ValueError(f"max_queue must be >= 0 (None/0 = "
                             f"unbounded), got {self.max_queue}")
        if self.fetch_timeout_s is not None and self.fetch_timeout_s <= 0:
            raise ValueError(f"fetch_timeout_s must be > 0 (None = no "
                             f"watchdog), got {self.fetch_timeout_s}")
        if self.policy not in policy_mod.POLICIES:
            raise ValueError(f"policy must be one of {policy_mod.POLICIES}, "
                             f"got {self.policy!r}")
        for entry in self.tenant_weights:
            name, weight = entry
            validate_slo_fields(name, None)
            if not float(weight) > 0:
                raise ValueError(f"tenant weight must be > 0, got "
                                 f"{name}={weight}")
        if self.tenant_quota is not None and self.tenant_quota < 0:
            raise ValueError(f"tenant_quota must be >= 0 (None/0 = "
                             f"unbounded), got {self.tenant_quota}")
        if self.trace_buffer < 0:
            raise ValueError(f"trace_buffer must be >= 0 (0 disables "
                             f"recording), got {self.trace_buffer}")
        if self.trace and self.trace_buffer == 0:
            raise ValueError("trace export needs trace_buffer > 0 (the "
                             "export is the event ring's contents)")
        for entry in self.slo_targets:
            cls, target = entry
            validate_slo_fields(None, cls)
            if not 0.0 < float(target) < 1.0:
                raise ValueError(f"SLO target must be in (0, 1), got "
                                 f"{cls}={target}")
        if self.slo_burn_threshold <= 0:
            raise ValueError(f"slo_burn_threshold must be > 0, got "
                             f"{self.slo_burn_threshold}")
        if self.slo_fast_window_s <= 0 or self.slo_slow_window_s <= 0:
            raise ValueError("SLO burn windows must be > 0 seconds, got "
                             f"{self.slo_fast_window_s}/"
                             f"{self.slo_slow_window_s}")
        if self.mem_poll_every < 0:
            raise ValueError(f"mem_poll_every must be >= 0 (0 = never "
                             f"sample), got {self.mem_poll_every}")
        if self.lane_kernel not in LANE_KERNELS:
            raise ValueError(f"lane_kernel must be one of {LANE_KERNELS}, "
                             f"got {self.lane_kernel!r}")
        if self.mega_lanes is not None and self.mega_lanes < 0:
            raise ValueError(f"mega_lanes must be >= 0 (None = auto: 1 on "
                             f"a multi-device mesh, 0 single-device), got "
                             f"{self.mega_lanes}")
        if not self.steady_tol > 0:
            raise ValueError(f"steady_tol must be > 0, got "
                             f"{self.steady_tol}")
        if self.numerics_guard not in ("warn", "quarantine"):
            raise ValueError(f"numerics_guard must be 'warn' or "
                             f"'quarantine', got {self.numerics_guard!r}")
        if self.engine_ckpt_interval < 0:
            raise ValueError(f"engine_ckpt_interval must be >= 0 (0 = "
                             f"off), got {self.engine_ckpt_interval}")
        if self.cache_max_bytes < 0:
            raise ValueError(f"cache_max_bytes must be >= 0 (0 = "
                             f"unbounded), got {self.cache_max_bytes}")
        if self.inject:
            # fail at construction, not at a boundary mid-drain (same
            # parse-time contract as HeatConfig.inject)
            faults.parse_spec(self.inject)


# --serve-on-nan rollback: restores a flagged lane at most this many times
# per request before declaring the blow-up deterministic — the per-lane
# mirror of backends/common.py's _MAX_ROLLBACKS_PER_STEP contract.
_MAX_LANE_ROLLBACKS = 2


def mega_device_count() -> int:
    """Devices a mega-lane mesh could span on THIS host — the seam the
    auto ``--mega-lanes`` default and the overflow rejection text resolve
    through (tests fake a single-device host by patching it)."""
    import jax

    return len(jax.devices())


@dataclasses.dataclass
class Request:
    """One admitted solve request."""

    id: str
    cfg: HeatConfig
    submit_t: float
    key: Optional[BucketKey] = None   # None once rejected, and for
                                      # mega-placed requests (their
                                      # "bucket" is the device mesh)
    placement: str = "packed"         # "packed" (vmapped bucket lanes) |
                                      # "mega" (mesh-spanning sharded
                                      # lane) — the ISSUE-10 second tier
    deadline_t: Optional[float] = None  # absolute wall deadline (engine
                                        # clock), resolved at submit from
                                        # the request's deadline_ms or the
                                        # engine default; None = none
    tenant: str = DEFAULT_TENANT        # fair-share / quota accounting key
    slo_class: str = DEFAULT_SLO_CLASS  # SLO class (config.SLO_CLASSES)
    seq: int = 0                        # engine-wide submit counter: the
                                        # FIFO order and every policy's
                                        # deterministic tiebreak
    trace_id: str = ""                  # request-scoped trace/flow id
                                        # (runtime/trace.py), minted at
                                        # submit and echoed in the record
    until: str = "steps"                # completion semantics (config.
                                        # UNTIL_MODES): "steps" runs all
                                        # ntime steps bit-for-bit as
                                        # before; "steady" retires at the
                                        # first chunk boundary whose
                                        # residual EWMA passes tolerance
    tol: Optional[float] = None         # per-request steady tolerance
                                        # (until=steady only; None = the
                                        # engine-wide --steady-tol)
    predicted_steps: Optional[int] = None  # closed-form eigenmode ETA to
                                        # steady, minted at submit
                                        # (runtime/convergence.py): the
                                        # EDF predicted-finish rank and
                                        # the trace's predicted-vs-actual
                                        # retirement boundary
    restore: Optional[dict] = None      # engine-state resume payload
                                        # (serve/resume.py): the
                                        # checkpointed host field ("T"),
                                        # "remaining", the cumulative
                                        # "chunks" meter, and the saved
                                        # "numerics" detector state; the
                                        # admitting _fill consumes it —
                                        # None for every normal request


def _bucket_for(cfg: HeatConfig, buckets) -> Optional[int]:
    """Smallest bucket side that fits the request, or None (overflow)."""
    for b in sorted(buckets):
        if cfg.n <= b:
            return b
    return None


def _write_result(out_dir, req_id: str, T: np.ndarray, cfg: HeatConfig,
                  steps: Optional[int] = None):
    """Atomic-publish one request's final field (same torn-file discipline
    as runtime/checkpoint.py: temp name outside any discovery glob).
    ``steps`` is the step count the field actually carries — below
    ``cfg.ntime`` for a steady early exit."""
    from pathlib import Path

    d = Path(out_dir)
    d.mkdir(parents=True, exist_ok=True)
    path = d / f"{req_id}.npz"
    tmp = d / (path.name + ".tmp")
    with open(tmp, "wb") as f:
        np.savez_compressed(f, T=np.asarray(T),
                            step=cfg.ntime if steps is None else int(steps),
                            n=cfg.n, ndim=cfg.ndim, dtype=cfg.dtype)
    tmp.rename(path)
    return path


class _GroupRunner:
    """Dispatch-ahead continuous batching for ONE bucket group.

    Owns the group's ``LaneEngine``, occupancy, the host-side countdown
    mirror (``dev_rem`` — exact, because the device decrements remaining
    by one per step while positive), and the in-flight deque of
    ``(seq, remaining-handle, predicted-vector)`` chunk boundaries.
    ``Engine.run`` drives many runners round-robin; each tick dispatches
    until ``dispatch_depth`` chunks are queued, then takes at most one
    boundary (the oldest handle).
    """

    def __init__(self, outer: "Engine", key: BucketKey, q,
                 writer: "async_io.SnapshotWriter"):
        self.outer = outer
        self.key = key
        self.q = q
        self.writer = writer
        scfg = outer.scfg
        self.chunk = scfg.chunk
        self.depth = max(1, scfg.dispatch_depth)
        self.rollback = scfg.on_nan == "rollback"
        self.lanes = lane_tier(min(len(q), scfg.lanes), scfg.lanes)
        # per-bucket kernel resolution (--serve-lane-kernel): a requested
        # Pallas program this bucket cannot run degrades loudly to the
        # XLA oracle — structured record + counter, never an error.
        # Rollback mode drops donation so in-flight boundary snapshots
        # are plain references to the undonated input stacks (no per-
        # chunk copy program on the dispatch path — engine.snapshot_stack)
        self.kernel, self._kernel_fb = resolve_lane_kernel(
            scfg.lane_kernel, key)
        # trace tracks (runtime/trace.py): one process row per bucket
        # group, one thread row per lane (the occupancy timeline) plus a
        # dispatch row for dispatch, chunk-in-flight, lane-load and
        # device-idle spans. Registered here, once, so the per-event path
        # is append-only.
        self.tracer = outer.tracer
        self.track_name = (f"lanes {key.ndim}d n{key.n} "
                           f"{key.dtype} {key.bc}")
        self.group_track = self.tracer.track(self.track_name, "dispatch")
        self.lane_tracks = [self.tracer.track(self.track_name, f"lane {i}")
                            for i in range(self.lanes)]
        self.eng = LaneEngine(key, self.lanes, scfg.chunk,
                              compiled_cache=outer._compiled,
                              on_compile=outer._note_compile,
                              kernel=self.kernel,
                              donate=not self.rollback,
                              tracer=self.tracer, track=self.group_track)
        if self._kernel_fb is not None:
            outer._note_lane_fallback(key, self.lanes, self._kernel_fb)
        self.occupant: List[Optional[Request]] = [None] * self.lanes
        # first dispatch seq whose chunk covers the lane's CURRENT
        # occupant: an in-flight chunk older than the epoch shows the
        # PREVIOUS occupant's state (zeros, or a quarantined NaN field)
        # and must not finish — or re-flag — the new one
        self.epoch = [0] * self.lanes
        self.dev_rem = np.zeros(self.lanes, dtype=np.int64)
        # per-lane fault-domain state, (re)set at each admission:
        # pending lane-nan poison thresholds, rollback retries left, and
        # the last verified-finite boundary (stack snapshot, steps left)
        self.nan_pending: List[List[int]] = [[] for _ in range(self.lanes)]
        # pending (step, eps) perturb events — the numerics observatory's
        # chaos channel (finite bump, so the isfinite bit never drops)
        self.perturb_pending: List[List[tuple]] = [
            [] for _ in range(self.lanes)]
        self.rb_left = [0] * self.lanes
        self.last_good: List[Optional[tuple]] = [None] * self.lanes
        # semantic scheduling (ISSUE 16): remaining-at-detection for a
        # lane whose until=steady occupant passed tolerance this
        # boundary; the judge pass consumes it (same process_boundary
        # call — _ingest_numerics runs first) and retires the lane at
        # its dispatch frontier
        self.steady_exit: List[Optional[int]] = [None] * self.lanes
        self.seq = 0                        # next dispatch's sequence id
        self.inflight: collections.deque = collections.deque()
        self.idle_from: Optional[float] = None  # group device queue empty
                                                # since (boundary gaps only)
        # cost-observatory feed (runtime/prof.py): the model key names the
        # bucket geometry; per-lane chunk counters back the usage stamps
        # (one vectorized add per dispatch — no per-lane python loop, no
        # device work); last_fetch_t makes the boundary service-time
        # estimator exact under pipelining (see prof.CostModel)
        self.cost_label = f"{key.ndim}d/n{key.n}/{key.dtype}/{key.bc}"
        self.lane_chunks = np.zeros(self.lanes, dtype=np.int64)
        self.last_fetch_t: Optional[float] = None
        self.allow_growth = False   # online loop opts in: offline run()
                                    # sizes runners from the full queue,
                                    # so growth (and its pipeline drain)
                                    # must never perturb the batch shape
        self._fill()

    # --- admission into lanes --------------------------------------------
    def _fill(self) -> None:
        """Swap queued requests into every free lane (continuous
        batching). The IC build + H2D load run on the scheduler thread,
        but with chunks in flight they overlap device compute instead of
        extending a fence. Queued requests already past their deadline
        are shed here — failing fast beats occupying a lane for a result
        nobody is waiting for. Pops happen under the engine lock (the
        gateway's HTTP threads push concurrently); which request pops is
        the admission policy's call (serve/policy.py), recorded in
        ``Engine.admission_trace``."""
        outer = self.outer
        if outer._ckpt_pause:
            # checkpoint bubble: no new admissions while the engine is
            # draining its pipeline toward the consistent cut — queued
            # requests are part of the manifest, not of a lane
            return
        for lane in range(self.lanes):
            while self.occupant[lane] is None and self.q:
                with outer._lock:
                    req = self.q.pop()
                    if req is None:
                        break
                    outer._queued_by_tenant[req.tenant] -= 1
                    outer.admission_trace.append(req.id)
                now = wall_clock()
                tr = self.tracer
                if tr.enabled:
                    # queue-wait span (pop side — serve/policy.py): the
                    # request's wait under THIS policy, id-paired so
                    # overlapping waits of one tenant render cleanly
                    policy_mod.note_pop(tr, outer.scfg.policy, req, now)
                cut = outer._deadline_cut(req, now)
                if cut is not None:
                    if tr.enabled:
                        tr.instant("deadline-shed", self.group_track,
                                   trace_id=req.trace_id,
                                   args={"id": req.id}, ts=now)
                    outer._fail_request(
                        req, "deadline",
                        "deadline: cancelled (deadline-preemption) while "
                        "still queued (never admitted)"
                        if cut == "cancelled" else
                        f"deadline: exceeded its "
                        f"{1e3 * (req.deadline_t - req.submit_t):.0f} ms "
                        f"budget while still queued (never admitted)")
                    outer.deadline_misses += 1
                    continue
                if tr.enabled:
                    tr.flow("t", self.lane_tracks[lane], req.trace_id,
                            ts=now)
                rec = outer._by_id[req.id]
                with outer._lock:
                    rec["lane"] = lane
                    rec["queue_wait_s"] = round(now - req.submit_t, 6)
                    rec["status"] = "running"
                    rec["_start_t"] = now
                rst = req.restore
                if rst:
                    # engine-state resume (serve/resume.py): re-seed the
                    # lane from the checkpointed field at its last
                    # boundary — the maybe_grow transplant contract, so
                    # continuation is bit-identical to an uninterrupted
                    # run. The chunk meter continues where it stopped:
                    # usage stamps stay cumulative across incarnations.
                    req.restore = None
                    self.eng.load_lane(lane, rst["T"], float(req.cfg.r),
                                       int(rst["remaining"]),
                                       req.cfg.bc_value)
                    self.dev_rem[lane] = int(rst["remaining"])
                    self.lane_chunks[lane] = int(rst.get("chunks", 0))
                else:
                    T0 = initial_condition(req.cfg)
                    self.eng.load_lane(lane, T0, float(req.cfg.r),
                                       req.cfg.ntime, req.cfg.bc_value)
                    self.dev_rem[lane] = req.cfg.ntime
                    self.lane_chunks[lane] = 0   # usage meter restarts
                                                 # with the new occupant
                self.occupant[lane] = req
                self.epoch[lane] = self.seq
                self.nan_pending[lane] = outer._lane_nan_steps(req)
                self.perturb_pending[lane] = outer._lane_perturb_events(req)
                if self.nan_pending[lane] or self.perturb_pending[lane]:
                    outer._has_lane_faults = True  # gates _maybe_poison
                self.rb_left[lane] = _MAX_LANE_ROLLBACKS
                self.last_good[lane] = None
                self.steady_exit[lane] = None   # never inherit a prior
                                                # occupant's verdict
                if outer.numerics is not None:
                    # arm the detectors: the analytic IC/BC envelope (zero
                    # device work, zero host scans — grid.ic_envelope),
                    # plus the request's steady tolerance and the closed-
                    # form eigenmode rate seeding the ETA fuser
                    lo, hi = ic_envelope(req.cfg)
                    outer.numerics.admit(
                        req.id, lo, hi, req.cfg.dtype, steady_tol=req.tol,
                        log_rate=conv_mod.closed_form_log_rate(req.cfg))
                    if rst and rst.get("numerics"):
                        # resume continuity: EWMAs, fired latches, and
                        # the ETA fuser pick up where the checkpointed
                        # incarnation left them (until=steady lanes keep
                        # their convergence history)
                        outer.numerics.reseed(req.id, rst["numerics"])

    def _live_remaining(self) -> List[int]:
        return [int(self.dev_rem[i]) for i, o in enumerate(self.occupant)
                if o is not None and self.dev_rem[i] > 0]

    def _effective_remaining(self) -> List[int]:
        """Per-live-lane remaining WORK for tail sizing: the countdown
        mirror, tightened for ``until=steady`` occupants by the fused
        eigenmode/observed ETA (runtime/convergence.py via the numerics
        observatory). Prediction only moves the full-chunk -> tail-
        program switch earlier — same two compiled chunk sizes — and
        never changes results: a mispredicted lane just keeps taking
        tail chunks until its actual exit."""
        numerics = self.outer.numerics
        out = []
        for i, req in enumerate(self.occupant):
            rem = int(self.dev_rem[i])
            if req is None or rem <= 0:
                continue
            if req.until == "steady" and numerics is not None:
                eta = numerics.eta_steps(req.id)
                if eta is not None:
                    rem = min(rem, max(int(eta), 1))
            out.append(rem)
        return out

    # --- dispatch side ----------------------------------------------------
    def _maybe_poison(self) -> None:
        """lane-nan chaos: poison any occupied lane whose completed-step
        count (by the host countdown mirror, i.e. after every chunk
        already dispatched) has reached a pending threshold. Only ever
        called with an active fault plan — the no-fault hot path never
        touches this."""
        for lane, req in enumerate(self.occupant):
            if req is None or not (self.nan_pending[lane]
                                   or self.perturb_pending[lane]):
                continue
            done = req.cfg.ntime - int(self.dev_rem[lane])
            while self.nan_pending[lane] and done >= self.nan_pending[lane][0]:
                self.nan_pending[lane].pop(0)   # fire-once per request
                self.eng.poison_lane(lane, req.cfg.n)
            while (self.perturb_pending[lane]
                   and done >= self.perturb_pending[lane][0][0]):
                _, eps = self.perturb_pending[lane].pop(0)  # fire-once
                self.eng.perturb_lane(lane, req.cfg.n, eps)

    def dispatch_fill(self) -> None:
        """Queue chunk programs until ``dispatch_depth`` are in flight or
        no lane has steps left to run. Pure host->device enqueue: no
        fetch, no fence (a rollback-mode stack snapshot is a device-side
        copy, also enqueued without a fence)."""
        if self.outer._ckpt_pause:
            # checkpoint bubble: stop feeding the pipeline so the
            # in-flight chunks drain to the empty cut (_ckpt_tick)
            return
        poison = self.outer._has_lane_faults
        while len(self.inflight) < self.depth:
            if self.allow_growth and self._growth_wanted():
                # stop feeding the pipeline: once the in-flight chunks
                # drain, maybe_grow rebuilds the group at the wider tier
                # (a short deliberate bubble instead of a burst serving
                # single-lane indefinitely)
                break
            live = self._live_remaining()
            if not live:
                break
            if poison:
                self._maybe_poison()
            k = self.chunk
            tail = self.eng.tail
            if (tail is not None
                    and max(self._effective_remaining()) <= self.chunk - tail):
                # every live lane finishes (or is PREDICTED to steady-
                # exit) inside the chunk, with enough headroom that
                # ceil(rem/tail) tail programs compute strictly fewer
                # masked steps than one full chunk
                k = tail
                self.outer.tail_chunks += 1
            t_disp = wall_clock()
            with self.tracer.span("heat.sched.dispatch", self.group_track,
                                  cat="dispatch",
                                  args={"seq": self.seq, "k": k}):
                handle = self.eng.dispatch_chunk(k)
            self._count_chunk(k)
            if self.idle_from is not None:
                self.outer.device_idle_s += t_disp - self.idle_from
                if self.tracer.enabled:
                    # the idle gap, ATTRIBUTED: this exact interval on
                    # this exact group's dispatch row had nothing queued
                    self.tracer.complete("device-idle", self.group_track,
                                         self.idle_from, t_disp, cat="idle")
                self.idle_from = None
            # usage metering: every lane still counting down participates
            # in this chunk (one vectorized add; freed lanes' garbage
            # counts are reset at the next admission)
            self.lane_chunks += self.dev_rem > 0
            np.maximum(self.dev_rem - k, 0, out=self.dev_rem)
            # rollback mode keeps every in-flight boundary restorable:
            # the snapshot is promoted to a lane's last_good only once
            # that boundary's finite bit comes back clean
            snap = self.eng.snapshot_stack() if self.rollback else None
            self.inflight.append(
                (self.seq, handle, self.dev_rem.astype(np.int32), snap,
                 t_disp, k))
            self.seq += 1
            self.outer.chunks_dispatched += 1

    def _count_chunk(self, k: int) -> None:
        """The lane counters (runtime/trace.py COUNTERS) of one k-step chunk
        over every slot. Call before the countdown mirror moves: a live lane
        takes min(k, its remaining) steps of the chunk."""
        live = useful = 0
        for req, rem in zip(self.occupant, self.dev_rem):
            if req is not None and rem > 0:
                live += 1
                useful += req.cfg.points * min(k, int(rem))
        tr = self.tracer
        tr.count("heat.lane.points_useful", useful)
        tr.count("heat.lane.points_computed",
                 self.lanes * self.eng.slab_points * k)
        tr.count("heat.lane.slots", self.lanes)
        tr.count("heat.lane.live", live)

    # --- boundary side ----------------------------------------------------
    def _fetch(self, handle) -> np.ndarray:
        """One watchdog-bounded boundary fetch with wall accounting."""
        outer = self.outer
        t0 = wall_clock()
        tr = self.tracer
        try:
            # boundary_wait_s, attributed: each fetch's blocked wall
            # becomes one span on the scheduler thread's row
            with tr.span("heat.sched.boundary_fetch",
                         tr.thread_track("scheduler") if tr.enabled
                         else None,
                         cat="boundary", args={"bucket": self.track_name}):
                return self.eng.fetch_remaining(
                    handle, timeout_s=outer.scfg.fetch_timeout_s,
                    plan=outer._plan, fetch_index=outer._fetch_seq)
        finally:
            outer._fetch_seq += 1
            outer.boundary_wait_s += wall_clock() - t0
            outer.boundary_waits += 1

    def _trace_occupancy(self, lane: int, req: Request, status: str) -> None:
        """Close the lane's occupancy span (admission -> this verdict) on
        its track. Must run BEFORE the finish/fail path pops the record's
        ``_start_t``."""
        tr = self.tracer
        if not tr.enabled:
            return
        t0 = self.outer._by_id[req.id].get("_start_t")
        if t0 is None:
            return
        tr.complete(req.id, self.lane_tracks[lane], t0, cat="lane",
                    trace_id=req.trace_id,
                    args={"status": status, "n": req.cfg.n,
                          "ntime": req.cfg.ntime})
        tr.flow("t", self.lane_tracks[lane], req.trace_id)

    def _judge_lanes(self, seq: int, rem, finite, snap, sync: bool) -> None:
        """Apply one fetched boundary's verdicts to every lane this
        boundary is authoritative for (epoch guard: a chunk dispatched
        before a lane's occupant swap or rollback must not judge the new
        state). Order per lane: health first (a non-finite result must
        never be delivered, even one that 'finished'), then completion,
        then deadline, then last-good promotion."""
        outer = self.outer
        now = wall_clock()
        for lane in range(self.lanes):
            req = self.occupant[lane]
            if req is None or seq < self.epoch[lane]:
                continue
            if finite is not None and not finite[lane]:
                self._handle_nonfinite(lane, req, int(rem[lane]), snap)
            elif rem[lane] == 0 or self.steady_exit[lane] is not None:
                steady_at = self.steady_exit[lane]
                self.steady_exit[lane] = None
                chunks = int(self.lane_chunks[lane])
                steps_done = req.cfg.ntime
                exit_mode = "steps"
                if steady_at is not None:
                    # steady exit retires at the dispatch FRONTIER: the
                    # chunks already in flight keep executing (the
                    # countdown mirror is untouched — the desync check
                    # stays exact) and the retirement snapshot is
                    # enqueued behind them, so the delivered field has
                    # exactly ntime - dev_rem steps — bit-identical to a
                    # fixed-step run truncated there, with zero new
                    # transfers. At depth 0 the frontier IS the
                    # detection boundary. A pipeline that already
                    # dispatched every step simply retires normally.
                    steps_done = req.cfg.ntime - int(self.dev_rem[lane])
                    if steps_done < req.cfg.ntime:
                        exit_mode = "steady"
                        outer.steady_exits += 1
                        # steps_saved_total is also bumped by the
                        # client-thread cache consult (_cache_replay) —
                        # cross-thread now, so every write takes the lock
                        with outer._lock:
                            outer.steps_saved_total += (req.cfg.ntime
                                                        - steps_done)
                        if self.tracer.enabled:
                            self.tracer.instant(
                                "steady-exit", self.lane_tracks[lane],
                                trace_id=req.trace_id,
                                args={"id": req.id, "at_step": steps_done,
                                      "requested": req.cfg.ntime,
                                      "saved": req.cfg.ntime - steps_done,
                                      "predicted_at_step":
                                          req.predicted_steps})
                self._trace_occupancy(lane, req, "retired")
                if sync:
                    outer._finish_sync(self.eng, lane, req, self.writer,
                                       chunks=chunks,
                                       steps_done=steps_done,
                                       exit_mode=exit_mode)
                else:
                    outer._finish_async(self.eng, lane, req, self.writer,
                                        chunks=chunks,
                                        steps_done=steps_done,
                                        exit_mode=exit_mode)
                self.occupant[lane] = None
            elif (cut := outer._deadline_cut(req, now)) is not None:
                done = req.cfg.ntime - int(rem[lane])
                self._trace_occupancy(lane, req, "deadline")
                outer._fail_request(
                    req, "deadline",
                    (f"deadline: cancelled (deadline-preemption) with "
                     f"~{done} of {req.cfg.ntime} steps done; lane "
                     f"{lane} preempted at the chunk boundary"
                     if cut == "cancelled" else
                     f"deadline: exceeded its "
                     f"{1e3 * (req.deadline_t - req.submit_t):.0f} ms "
                     f"budget with ~{done} of {req.cfg.ntime} steps done; "
                     f"lane {lane} preempted at the chunk boundary"),
                    lane=lane,
                    steps_done=done, chunks=int(self.lane_chunks[lane]))
                outer.deadline_misses += 1
                # the lane keeps counting down on device (masked garbage
                # until refilled) so the host mirror stays exact; a
                # refill overwrites buffer + countdown wholesale
                self.occupant[lane] = None
            elif self.rollback and snap is not None:
                self.last_good[lane] = (snap, int(rem[lane]))

    def _handle_nonfinite(self, lane: int, req: Request, rem_at: int,
                          snap) -> None:
        """One lane's finite bit dropped: restore-and-re-step it alone
        (rollback mode, budget permitting) or quarantine the request.
        Either way every other lane is untouched."""
        outer = self.outer
        done = req.cfg.ntime - rem_at
        if self.rollback and self.rb_left[lane] > 0:
            self.rb_left[lane] -= 1
            outer.rollbacks += 1
            if self.tracer.enabled:
                self.tracer.instant("rollback", self.lane_tracks[lane],
                                    trace_id=req.trace_id,
                                    args={"id": req.id, "at_step": done})
            if self.last_good[lane] is not None:
                good_snap, steps_left = self.last_good[lane]
                master_print(
                    f"serve on-nan rollback: request {req.id} (lane {lane}) "
                    f"non-finite at ~step {done}; restoring the last "
                    f"verified boundary ({steps_left} steps left, attempt "
                    f"{_MAX_LANE_ROLLBACKS - self.rb_left[lane]}/"
                    f"{_MAX_LANE_ROLLBACKS})")
                self.eng.restore_lane(lane, good_snap[lane],
                                      float(req.cfg.r), req.cfg.n,
                                      steps_left)
                self.dev_rem[lane] = steps_left
            else:
                # no verified boundary yet: re-admit from the (determin-
                # istic) initial condition — the first-chunk transient
                master_print(
                    f"serve on-nan rollback: request {req.id} (lane {lane}) "
                    f"non-finite at ~step {done}; re-stepping from the "
                    f"initial condition (attempt "
                    f"{_MAX_LANE_ROLLBACKS - self.rb_left[lane]}/"
                    f"{_MAX_LANE_ROLLBACKS})")
                T0 = initial_condition(req.cfg)
                self.eng.load_lane(lane, T0, float(req.cfg.r),
                                   req.cfg.ntime, req.cfg.bc_value)
                self.dev_rem[lane] = req.cfg.ntime
            # boundaries already in flight show the pre-restore (still
            # poisoned) lane: the epoch bump makes them non-authoritative
            self.epoch[lane] = self.seq
            self.last_good[lane] = None
        else:
            exhausted = self.rollback and self.rb_left[lane] == 0
            tried = (f" after {_MAX_LANE_ROLLBACKS} rollbacks "
                     f"(deterministic blow-up)" if exhausted else "")
            if self.tracer.enabled:
                self.tracer.instant("quarantine", self.lane_tracks[lane],
                                    trace_id=req.trace_id,
                                    args={"id": req.id, "at_step": done})
            self._trace_occupancy(lane, req, "nonfinite")
            outer._fail_request(
                req, "nonfinite",
                f"nonfinite: non-finite field detected at ~step {done} of "
                f"{req.cfg.ntime} (lane {lane}){tried} — check the CFL "
                f"bound sigma <= 1/(2*ndim) for this request", lane=lane,
                steps_done=done, chunks=int(self.lane_chunks[lane]))
            outer.lanes_quarantined += 1
            if exhausted:
                # flight-recorder trigger: a lane quarantined after its
                # rollback budget is the postmortem case the ring exists
                # for — the dump holds the whole restore/re-flag history
                outer._flight_dump("quarantine after "
                                   f"{_MAX_LANE_ROLLBACKS} rollbacks "
                                   f"(request {req.id})")
            # free the lane; its NaN field idles masked (and its device
            # countdown keeps draining, mirrored by dev_rem) until a new
            # request's load overwrites the whole lane buffer
            self.occupant[lane] = None
            self.nan_pending[lane] = []
            self.last_good[lane] = None

    def _ingest_numerics(self, seq: int, b: np.ndarray) -> None:
        """Feed one fetched boundary's fused stats rows (rows 2-5 of the
        widened vector — engine.unpack_boundary) to the numerics
        observatory and apply its verdicts. Runs BEFORE ``_judge_lanes``
        under the same epoch guard, so a quarantine verdict frees the
        lane before the health/completion pass sees it — and a stale
        chunk can never judge a swapped-in occupant's physics."""
        outer = self.outer
        stats = unpack_boundary(b)
        rem = b[0]
        tr = self.tracer
        for lane in range(self.lanes):
            req = self.occupant[lane]
            if req is None or seq < self.epoch[lane]:
                continue
            resid = float(stats[0, lane])
            heat = float(stats[3, lane])
            if tr.enabled:
                # Perfetto counter track: the lane's residual/heat as
                # 'C' series — the convergence sparkline on the timeline
                tr.counter(f"numerics lane {lane}", self.group_track,
                           {"resid": resid, "heat": heat})
            events = outer.numerics.observe(
                req.id, resid, float(stats[1, lane]),
                float(stats[2, lane]), heat, int(rem[lane]))
            for ev in events:
                outer._note_numerics_event(self, lane, req,
                                           int(rem[lane]), ev)

    def _quarantine_numerics(self, lane: int, req: Request, rem_at: int,
                             why: str) -> None:
        """``--numerics-guard quarantine``: a violated lane takes the
        PR-5 quarantine exit — structured nonfinite failure, lane freed,
        co-scheduled lanes byte-identical to a clean run (the masking
        contract confines the damage to the lane's own buffer)."""
        outer = self.outer
        done = req.cfg.ntime - rem_at
        if self.tracer.enabled:
            self.tracer.instant("quarantine", self.lane_tracks[lane],
                                trace_id=req.trace_id,
                                args={"id": req.id, "at_step": done,
                                      "why": why})
        self._trace_occupancy(lane, req, "nonfinite")
        outer._fail_request(
            req, "nonfinite",
            f"numerics: {why} violation at ~step {done} of "
            f"{req.cfg.ntime} (lane {lane}) — the field is finite but "
            f"un-physical; check r against the CFL bound "
            f"sigma <= 1/(2*ndim), dtype drift, or an injected perturb "
            f"fault (TROUBLESHOOTING.md)", lane=lane,
            steps_done=done, chunks=int(self.lane_chunks[lane]))
        outer.lanes_quarantined += 1
        self.occupant[lane] = None
        self.nan_pending[lane] = []
        self.perturb_pending[lane] = []
        self.last_good[lane] = None

    def process_boundary(self) -> None:
        """Take one chunk boundary: fetch the OLDEST in-flight boundary
        vector (the newer chunks keep computing behind the transfer),
        judge every lane's health/completion/deadline, refill from the
        queue."""
        if self.inflight:
            seq, handle, predicted, snap, t_disp, k = self.inflight.popleft()
            b = self._fetch(handle)
            t_done = wall_clock()
            rem, finite = b[0], b[1]
            if self.tracer.enabled:
                # chunk-in-flight span: dispatch enqueue -> boundary
                # fetched (under dispatch-ahead the newer chunks compute
                # behind this interval — visibly, on the timeline)
                self.tracer.complete(f"chunk {seq} ({k} steps)",
                                     self.group_track, t_disp, t_done,
                                     cat="chunk",
                                     args={"seq": seq, "k": k})
            outer = self.outer
            if outer.prof.enabled:
                # cost-model feed: boundary service time from timestamps
                # already taken — exact when fenced, per-chunk under a
                # saturated pipeline (prof.CostModel); then the cadenced
                # memory watermark sample, also off the dispatch path
                base = (t_disp if self.last_fetch_t is None
                        else max(self.last_fetch_t, t_disp))
                outer.prof.observe_chunk(self.cost_label, self.lanes,
                                         self.depth, k, t_done - base,
                                         kernel=self.kernel)
                self.last_fetch_t = t_done
                warn = outer.prof.maybe_sample_memory(t_done)
                if warn is not None:
                    outer._mem_warn(warn)
            if not self.inflight:
                self.idle_from = t_done
            if not np.array_equal(rem, predicted):
                raise RuntimeError(
                    f"serve dispatch-ahead desync for bucket {self.key}: "
                    f"device remaining {rem.tolist()} != host-predicted "
                    f"{predicted.tolist()} at chunk {seq} — the lane "
                    f"masking contract broke; results cannot be trusted")
            if outer.numerics is not None:
                self._ingest_numerics(seq, b)
            self._judge_lanes(seq, rem, finite, snap, sync=False)
            outer._note_boundary()
        else:
            # nothing in flight and nothing left to step: occupants whose
            # countdown is already settled at zero (ntime=0 admits, or
            # the final boundary was already inspected) retire directly
            self._judge_lanes(self.seq, self.dev_rem, None, None,
                              sync=False)
        self._fill()

    def has_work(self) -> bool:
        return (bool(self.inflight) or bool(self.q)
                or any(o is not None for o in self.occupant))

    # --- online lane-tier growth ------------------------------------------
    def _growth_wanted(self) -> bool:
        if self.lanes >= self.outer.scfg.lanes:
            return False
        occupied = sum(o is not None for o in self.occupant)
        want = lane_tier(max(1, min(occupied + len(self.q),
                                    self.outer.scfg.lanes)),
                         self.outer.scfg.lanes)
        return want > self.lanes

    def maybe_grow(self) -> None:
        """Streaming admission can outgrow the lane tier this runner was
        born with (the first online request builds a tier-1 group; a
        burst then queues behind one lane). At an empty-pipeline boundary
        — no chunk in flight, so the live stack IS the last judged state
        — rebuild the group at the demanded tier and transplant every
        occupant bit-exactly: crop its field out (one D2H), reload it
        into the wider stack with the same remaining count (the host
        countdown mirror is exact by construction). Bounded cost: tiers
        are powers of two capped at ``--lanes``, so a group grows at most
        log2(lanes) times for its whole lifetime. Offline ``run()`` sizes
        runners from the full queue up front, so this never fires there
        (the PR-3..5 admission traces stay byte-identical)."""
        outer = self.outer
        if self.inflight or not self.allow_growth or not self._growth_wanted():
            return
        occupied = sum(o is not None for o in self.occupant)
        want = lane_tier(max(1, min(occupied + len(self.q),
                                    outer.scfg.lanes)), outer.scfg.lanes)
        old_eng, old_occ = self.eng, self.occupant
        old_rem, old_nan, old_rb = self.dev_rem, self.nan_pending, self.rb_left
        old_chunks, old_pert = self.lane_chunks, self.perturb_pending
        old_steady = self.steady_exit
        if self.tracer.enabled:
            self.tracer.instant("lane-tier-grow", self.group_track,
                                args={"from": self.lanes, "to": want})
        self.lanes = want
        self.eng = LaneEngine(self.key, want, self.chunk,
                              compiled_cache=outer._compiled,
                              on_compile=outer._note_compile,
                              kernel=self.kernel,
                              donate=not self.rollback,
                              tracer=self.tracer, track=self.group_track)
        if self._kernel_fb is not None:
            # the fallback contract is per (bucket, tier): the grown tier
            # is a new compiled program that also fell back
            outer._note_lane_fallback(self.key, want, self._kernel_fb)
        self.occupant = [None] * want
        self.epoch = [self.seq] * want
        self.dev_rem = np.zeros(want, dtype=np.int64)
        self.lane_chunks = np.zeros(want, dtype=np.int64)
        self.nan_pending = [[] for _ in range(want)]
        self.perturb_pending = [[] for _ in range(want)]
        self.rb_left = [0] * want
        self.last_good = [None] * want
        self.steady_exit = [None] * want
        self.lane_tracks = [self.tracer.track(self.track_name, f"lane {i}")
                            for i in range(want)]
        for lane, req in enumerate(old_occ):
            if req is None:
                continue
            T = old_eng.extract_lane(lane, req.cfg.n)
            self.eng.load_lane(lane, T, float(req.cfg.r),
                               int(old_rem[lane]), req.cfg.bc_value)
            self.occupant[lane] = req
            self.dev_rem[lane] = old_rem[lane]
            self.lane_chunks[lane] = old_chunks[lane]
            self.nan_pending[lane] = old_nan[lane]
            self.perturb_pending[lane] = old_pert[lane]
            self.rb_left[lane] = old_rb[lane]
            self.steady_exit[lane] = old_steady[lane]
            # the old tier's stack snapshots have the old lane count: drop
            # them; a post-growth rollback re-steps from the IC instead
        outer.lane_grows += 1
        self._fill()

    # --- synchronous fallback (--dispatch-depth off) ----------------------
    def sync_round(self) -> None:
        """One fenced boundary of the PR-3 shape: dispatch a chunk, fetch
        it immediately (the fetch fences the chunk), judge every lane on
        the scheduler thread, refill. ``run_sync`` loops it to drain; the
        online loop calls it round-robin across groups so depth-0 engines
        still stream admissions."""
        outer = self.outer
        finite = None
        snap = None
        if self._live_remaining():
            if outer._has_lane_faults:
                self._maybe_poison()
            t0 = wall_clock()
            if self.idle_from is not None:
                # device sat idle from the last fetch's return until
                # this dispatch — the fence cost the A/B demonstrates
                outer.device_idle_s += t0 - self.idle_from
                if self.tracer.enabled:
                    self.tracer.complete("device-idle", self.group_track,
                                         self.idle_from, t0, cat="idle")
            with self.tracer.span("heat.sched.dispatch", self.group_track,
                                  cat="dispatch",
                                  args={"seq": self.seq, "k": self.chunk}):
                handle = self.eng.dispatch_chunk()
            self._count_chunk(self.chunk)
            b = self._fetch(handle)
            rem, finite = b[0], b[1]
            outer.chunks_dispatched += 1
            self.idle_from = wall_clock()
            if self.tracer.enabled:
                self.tracer.complete(f"chunk {self.seq} ({self.chunk} "
                                     f"steps, fenced)", self.group_track,
                                     t0, self.idle_from, cat="chunk",
                                     args={"seq": self.seq,
                                           "k": self.chunk})
            if outer.prof.enabled:
                # fenced boundary: the dispatch->fetch wall IS the chunk
                # service time (cost-model key depth 0, the sync shape)
                outer.prof.observe_chunk(self.cost_label, self.lanes, 0,
                                         self.chunk, self.idle_from - t0,
                                         kernel=self.kernel)
                warn = outer.prof.maybe_sample_memory(self.idle_from)
                if warn is not None:
                    outer._mem_warn(warn)
            self.lane_chunks += self.dev_rem > 0
            np.maximum(self.dev_rem - self.chunk, 0, out=self.dev_rem)
            if self.rollback:
                snap = self.eng.snapshot_stack()
            if outer.numerics is not None:
                self._ingest_numerics(self.seq, b)
            outer._note_boundary()
        else:
            rem = self.dev_rem
        self._judge_lanes(self.seq, rem, finite, snap, sync=True)
        self.seq += 1
        self._fill()

    def run_sync(self) -> None:
        """The PR-3 shape, kept for debugging A/Bs: fetch every boundary
        as its chunk is dispatched (the fetch fences the whole chunk) and
        extract finished lanes on the scheduler thread. No pipelining, no
        tail programs — but the same per-lane fault domains: the boundary
        vector carries the finite bits either way, and here the live
        stack IS the fetched boundary's state, so rollback snapshots are
        taken after the fetch, from a boundary already judged."""
        while self.has_work():
            self.sync_round()
            # every fenced round is an empty-pipeline cut: take an armed
            # engine checkpoint here (depth > 0 ticks in the drive loops)
            self.outer._ckpt_tick()


class MegaLaneRunner:
    """Dispatch-ahead serving for ONE mesh-spanning mega-lane slot.

    The second placement tier (ISSUE 10): a ``_GroupRunner`` peer whose
    "bucket group" is the whole device mesh and whose lane count is one.
    Requests that overflow every bucket queue here (``Engine.submit``)
    and run as the sharded padded-carry chunked advance
    (``serve/engine.py MegaLaneEngine`` over ``backends/sharded.py``),
    wrapped in the exact contract the packed runners live by: a device
    boundary handle per chunk, ``--dispatch-depth`` chunks in flight, a
    host countdown mirror cross-checked against every fetch, the
    owned-cells ``isfinite`` bit riding the boundary D2H, and the
    deadline / quarantine / rollback / watchdog semantics of a fault
    domain whose blast radius is one mesh. ``Engine.run``'s round-robin
    treats it as just another group, so a mega-lane's boundary
    bookkeeping hides under packed-lane compute and vice versa — and,
    per the roofline note (PAPERS.md), the mega chunk's halo-exchange
    boundaries are exactly the slack packed-lane chunk dispatches fill.

    One slot serves one request at a time; ``--mega-lanes N`` slots
    share the engine-wide mega queue (admission order is the engine's
    policy, same as the packed tier). The mesh being a shared physical
    resource, a wedged mega fetch (watchdog) fails the whole mega tier's
    in-flight and queued requests — one mesh, one fault domain."""

    def __init__(self, outer: "Engine", slot: int, q,
                 writer: "async_io.SnapshotWriter"):
        self.outer = outer
        self.slot = slot
        self.q = q
        self.writer = writer
        scfg = outer.scfg
        self.chunk = scfg.chunk
        self.depth = max(1, scfg.dispatch_depth)
        self.rollback = scfg.on_nan == "rollback"
        self.lanes = 1
        self.kernel = "sharded"
        self.key = ("mega", slot)
        # single-lane mirrors of the group runner's per-lane state, so
        # Engine._fail_group (and the round-robin) treat both alike
        self.occupant: List[Optional[Request]] = [None]
        self.epoch = [0]
        self.dev_rem = np.zeros(1, dtype=np.int64)
        self.lane_chunks = np.zeros(1, dtype=np.int64)
        self.nan_pending: List[List[int]] = [[]]
        self.perturb_pending: List[List[tuple]] = [[]]
        self.rb_left = [0]
        self.last_good: List[Optional[tuple]] = [None]
        self.steady_exit: List[Optional[int]] = [None]
        self.seq = 0
        self.inflight: collections.deque = collections.deque()
        self.idle_from: Optional[float] = None
        self.allow_growth = False      # a mega-lane has no tier to grow:
                                       # it already spans the mesh
        self.eng = None                # MegaLaneEngine, per occupant
        self.cost_label = "mega"       # refined per occupant
        self.last_fetch_t: Optional[float] = None
        self.tracer = outer.tracer
        self.track_name = f"mega lane {slot}"
        self.group_track = self.tracer.track(self.track_name, "dispatch")
        self.lane_tracks = [self.tracer.track(self.track_name, "mesh")]
        self._fill()

    # --- admission --------------------------------------------------------
    def _fill(self) -> None:
        """Admit the next queued mega request into this slot: build the
        mesh-spanning engine (seed + AOT chunk compiles, warm via the
        engine-shared cache) on the scheduler thread. Queued requests
        past their deadline are shed here, and an engine-construction
        failure (a compile error on THIS config) fails that one request
        — never the scheduler loop."""
        outer = self.outer
        if outer._ckpt_pause:
            # checkpoint bubble: same no-new-admissions contract as the
            # packed tier — queued mega requests ride the manifest
            return
        while self.occupant[0] is None and self.q:
            with outer._lock:
                req = self.q.pop()
                if req is None:
                    break
                outer._queued_by_tenant[req.tenant] -= 1
                outer.admission_trace.append(req.id)
            now = wall_clock()
            tr = self.tracer
            if tr.enabled:
                policy_mod.note_pop(tr, outer.scfg.policy, req, now)
            cut = outer._deadline_cut(req, now)
            if cut is not None:
                if tr.enabled:
                    tr.instant("deadline-shed", self.group_track,
                               trace_id=req.trace_id,
                               args={"id": req.id}, ts=now)
                outer._fail_request(
                    req, "deadline",
                    "deadline: cancelled (deadline-preemption) while "
                    "still queued (never admitted)"
                    if cut == "cancelled" else
                    f"deadline: exceeded its "
                    f"{1e3 * (req.deadline_t - req.submit_t):.0f} ms "
                    f"budget while still queued (never admitted)")
                outer.deadline_misses += 1
                continue
            if tr.enabled:
                tr.flow("t", self.lane_tracks[0], req.trace_id, ts=now)
            rec = outer._by_id[req.id]
            with outer._lock:
                rec["lane"] = 0
                rec["queue_wait_s"] = round(now - req.submit_t, 6)
                rec["status"] = "running"
                rec["_start_t"] = now
            try:
                mesh = outer._mega_mesh(req.cfg.ndim)
                self.eng = MegaLaneEngine(
                    req.cfg, mesh, self.chunk,
                    compiled_cache=outer._compiled,
                    on_compile=outer._note_mega_compile)
            except Exception as e:  # noqa: BLE001 — per-request record
                outer._fail_request(
                    req, "error",
                    f"mega-lane build failed: {type(e).__name__}: {e}",
                    lane=0)
                continue
            self.cost_label = (f"{req.cfg.ndim}d/n{req.cfg.n}/"
                               f"{req.cfg.dtype}/{req.cfg.bc}")
            rst = req.restore
            if rst:
                # engine-state resume: overwrite the freshly seeded mesh
                # state with the checkpointed owned field (crop -> seed
                # round trip at a chunk boundary is bit-exact — the
                # owned-cell invariance argument of serve/engine.py)
                req.restore = None
                self.eng.load(rst["T"], int(rst["remaining"]))
                self.dev_rem[0] = int(rst["remaining"])
                self.lane_chunks[0] = int(rst.get("chunks", 0))
            else:
                self.dev_rem[0] = req.cfg.ntime
                self.lane_chunks[0] = 0
            self.occupant[0] = req
            self.epoch[0] = self.seq
            self.nan_pending[0] = outer._lane_nan_steps(req)
            self.perturb_pending[0] = outer._lane_perturb_events(req)
            if self.nan_pending[0] or self.perturb_pending[0]:
                outer._has_lane_faults = True
            self.rb_left[0] = _MAX_LANE_ROLLBACKS
            self.last_good[0] = None
            self.steady_exit[0] = None
            if outer.numerics is not None:
                lo, hi = ic_envelope(req.cfg)
                outer.numerics.admit(
                    req.id, lo, hi, req.cfg.dtype, steady_tol=req.tol,
                    log_rate=conv_mod.closed_form_log_rate(req.cfg))
                if rst and rst.get("numerics"):
                    outer.numerics.reseed(req.id, rst["numerics"])

    def maybe_grow(self) -> None:
        """Interface parity with ``_GroupRunner``: nothing to grow."""

    def has_work(self) -> bool:
        return (bool(self.inflight) or bool(self.q)
                or self.occupant[0] is not None)

    # --- dispatch side ----------------------------------------------------
    def _maybe_poison(self) -> None:
        req = self.occupant[0]
        if req is None or not (self.nan_pending[0]
                               or self.perturb_pending[0]):
            return
        done = req.cfg.ntime - int(self.dev_rem[0])
        while self.nan_pending[0] and done >= self.nan_pending[0][0]:
            self.nan_pending[0].pop(0)
            self.eng.poison_center()
        while (self.perturb_pending[0]
               and done >= self.perturb_pending[0][0][0]):
            _, eps = self.perturb_pending[0].pop(0)
            self.eng.perturb_center(eps)

    def dispatch_fill(self) -> None:
        """Queue mesh chunk programs until ``dispatch_depth`` are in
        flight or the occupant has no steps left. The chunk size shrinks
        to the exact remaining count on the final dispatch (the sharded
        advance has no per-step countdown mask — the host picks k, and
        the at-most-one remainder program was AOT-compiled at
        admission)."""
        outer = self.outer
        if outer._ckpt_pause:
            # checkpoint bubble: drain toward the empty cut
            return
        poison = outer._has_lane_faults
        while len(self.inflight) < self.depth:
            rem = int(self.dev_rem[0])
            if self.occupant[0] is None or rem <= 0:
                break
            if poison:
                self._maybe_poison()
            k = min(self.chunk, rem)
            t_disp = wall_clock()
            with self.tracer.span("heat.sched.dispatch", self.group_track,
                                  cat="dispatch",
                                  args={"seq": self.seq, "k": k}):
                handle = self.eng.dispatch_chunk(k)
            if self.idle_from is not None:
                outer.device_idle_s += t_disp - self.idle_from
                if self.tracer.enabled:
                    self.tracer.complete("device-idle", self.group_track,
                                         self.idle_from, t_disp, cat="idle")
                self.idle_from = None
            self.lane_chunks[0] += 1
            self.dev_rem[0] = rem - k
            snap = self.eng.snapshot_state() if self.rollback else None
            self.inflight.append(
                (self.seq, handle, self.dev_rem.astype(np.int32).copy(),
                 snap, t_disp, k))
            self.seq += 1
            outer.chunks_dispatched += 1

    # --- boundary side ----------------------------------------------------
    def _fetch(self, handle) -> np.ndarray:
        outer = self.outer
        t0 = wall_clock()
        tr = self.tracer
        try:
            with tr.span("heat.sched.boundary_fetch",
                         tr.thread_track("scheduler") if tr.enabled
                         else None,
                         cat="boundary", args={"bucket": self.track_name}):
                return engine_fetch_boundary(
                    handle, timeout_s=outer.scfg.fetch_timeout_s,
                    plan=outer._plan, fetch_index=outer._fetch_seq)
        finally:
            outer._fetch_seq += 1
            outer.boundary_wait_s += wall_clock() - t0
            outer.boundary_waits += 1

    def _trace_occupancy(self, lane: int, req: Request, status: str) -> None:
        tr = self.tracer
        if not tr.enabled:
            return
        t0 = self.outer._by_id[req.id].get("_start_t")
        if t0 is None:
            return
        tr.complete(req.id, self.lane_tracks[0], t0, cat="lane",
                    trace_id=req.trace_id,
                    args={"status": status, "n": req.cfg.n,
                          "ntime": req.cfg.ntime, "placement": "mega"})
        tr.flow("t", self.lane_tracks[0], req.trace_id)

    def _judge(self, seq: int, rem, finite, snap, sync: bool) -> None:
        """One boundary's verdict for the single mega-lane: health first
        (a non-finite field must never be delivered), then completion,
        then deadline, then last-good promotion — the ``_judge_lanes``
        order, one lane wide. The epoch guard keeps a chunk dispatched
        before a swap/rollback from judging the new occupant."""
        outer = self.outer
        now = wall_clock()
        req = self.occupant[0]
        if req is None or seq < self.epoch[0]:
            return
        if finite is not None and not finite[0]:
            self._handle_nonfinite(req, int(rem[0]), snap)
        elif rem[0] == 0 or self.steady_exit[0] is not None:
            self._retire(req, sync)
        elif (cut := outer._deadline_cut(req, now)) is not None:
            done = req.cfg.ntime - int(rem[0])
            self._trace_occupancy(0, req, "deadline")
            outer._fail_request(
                req, "deadline",
                (f"deadline: cancelled (deadline-preemption) with ~{done} "
                 f"of {req.cfg.ntime} steps done; mega lane preempted at "
                 f"the chunk boundary"
                 if cut == "cancelled" else
                 f"deadline: exceeded its "
                 f"{1e3 * (req.deadline_t - req.submit_t):.0f} ms budget "
                 f"with ~{done} of {req.cfg.ntime} steps done; mega lane "
                 f"preempted at the chunk boundary"), lane=0,
                steps_done=done, chunks=int(self.lane_chunks[0]))
            outer.deadline_misses += 1
            self._release()
        elif self.rollback and snap is not None:
            self.last_good[0] = (snap, int(rem[0]))

    def _release(self) -> None:
        """Free the slot (and the multi-shard state) after a terminal
        verdict; stale in-flight boundaries are drained by seq/epoch."""
        self.occupant[0] = None
        self.eng = None
        self.dev_rem[0] = 0
        self.nan_pending[0] = []
        self.perturb_pending[0] = []
        self.last_good[0] = None
        self.steady_exit[0] = None
        self.epoch[0] = self.seq

    def _handle_nonfinite(self, req: Request, rem_at: int, snap) -> None:
        """The mega-lane's finite bit dropped: restore-and-re-step the
        whole mesh state (rollback mode, budget permitting) or
        quarantine the request — packed lanes in other groups are
        untouched either way."""
        outer = self.outer
        done = req.cfg.ntime - rem_at
        if self.rollback and self.rb_left[0] > 0:
            self.rb_left[0] -= 1
            outer.rollbacks += 1
            if self.tracer.enabled:
                self.tracer.instant("rollback", self.lane_tracks[0],
                                    trace_id=req.trace_id,
                                    args={"id": req.id, "at_step": done})
            if self.last_good[0] is not None:
                good_snap, steps_left = self.last_good[0]
                master_print(
                    f"serve on-nan rollback: mega request {req.id} "
                    f"non-finite at ~step {done}; restoring the last "
                    f"verified boundary ({steps_left} steps left, attempt "
                    f"{_MAX_LANE_ROLLBACKS - self.rb_left[0]}/"
                    f"{_MAX_LANE_ROLLBACKS})")
                self.eng.restore(good_snap, steps_left)
                self.dev_rem[0] = steps_left
            else:
                master_print(
                    f"serve on-nan rollback: mega request {req.id} "
                    f"non-finite at ~step {done}; re-stepping from the "
                    f"initial condition (attempt "
                    f"{_MAX_LANE_ROLLBACKS - self.rb_left[0]}/"
                    f"{_MAX_LANE_ROLLBACKS})")
                self.eng.reload()
                self.dev_rem[0] = req.cfg.ntime
            self.epoch[0] = self.seq
            self.last_good[0] = None
        else:
            exhausted = self.rollback and self.rb_left[0] == 0
            tried = (f" after {_MAX_LANE_ROLLBACKS} rollbacks "
                     f"(deterministic blow-up)" if exhausted else "")
            if self.tracer.enabled:
                self.tracer.instant("quarantine", self.lane_tracks[0],
                                    trace_id=req.trace_id,
                                    args={"id": req.id, "at_step": done})
            self._trace_occupancy(0, req, "nonfinite")
            outer._fail_request(
                req, "nonfinite",
                f"nonfinite: non-finite field detected at ~step {done} of "
                f"{req.cfg.ntime} (mega lane){tried} — check the CFL "
                f"bound sigma <= 1/(2*ndim) for this request", lane=0,
                steps_done=done, chunks=int(self.lane_chunks[0]))
            outer.lanes_quarantined += 1
            if exhausted:
                outer._flight_dump("quarantine after "
                                   f"{_MAX_LANE_ROLLBACKS} rollbacks "
                                   f"(mega request {req.id})")
            self._release()

    def _ingest_numerics(self, seq: int, b: np.ndarray) -> None:
        """The mega mirror of ``_GroupRunner._ingest_numerics``: one
        lane, mesh-wide stats (the sharded advance's cross-shard
        min/max/sum merge — serve/engine.py mega boundary contract)."""
        outer = self.outer
        req = self.occupant[0]
        if req is None or seq < self.epoch[0]:
            return
        stats = unpack_boundary(b)
        resid, heat = float(stats[0, 0]), float(stats[3, 0])
        if self.tracer.enabled:
            self.tracer.counter("numerics mega", self.group_track,
                                {"resid": resid, "heat": heat})
        events = outer.numerics.observe(
            req.id, resid, float(stats[1, 0]), float(stats[2, 0]),
            heat, int(b[0][0]))
        for ev in events:
            outer._note_numerics_event(self, 0, req, int(b[0][0]), ev)

    def _quarantine_numerics(self, lane: int, req: Request, rem_at: int,
                             why: str) -> None:
        """``--numerics-guard quarantine`` for the mega tier: fail the
        occupant nonfinite and free the slot (packed groups untouched —
        the mesh is this request's whole fault domain)."""
        outer = self.outer
        done = req.cfg.ntime - rem_at
        if self.tracer.enabled:
            self.tracer.instant("quarantine", self.lane_tracks[0],
                                trace_id=req.trace_id,
                                args={"id": req.id, "at_step": done,
                                      "why": why})
        self._trace_occupancy(0, req, "nonfinite")
        outer._fail_request(
            req, "nonfinite",
            f"numerics: {why} violation at ~step {done} of "
            f"{req.cfg.ntime} (mega lane) — the field is finite but "
            f"un-physical; check r against the CFL bound "
            f"sigma <= 1/(2*ndim), dtype drift, or an injected perturb "
            f"fault (TROUBLESHOOTING.md)", lane=0,
            steps_done=done, chunks=int(self.lane_chunks[0]))
        outer.lanes_quarantined += 1
        self._release()

    def _retire(self, req: Request, sync: bool) -> None:
        """Completion: crop the padded state to the owned field (a device
        program, enqueued) and hand the D2H + npz publish to the writer
        thread — the mega mirror of ``_finish_async``/``_finish_sync``.
        The writeback closure holds only the cropped snapshot, so the
        padded mesh state is freed with the slot."""
        outer = self.outer
        steady_at = self.steady_exit[0]
        self.steady_exit[0] = None
        steps_done = req.cfg.ntime
        exit_mode = "steps"
        if steady_at is not None:
            # dispatch-frontier retirement, the _judge_lanes contract:
            # in-flight mega chunks still execute (countdown untouched),
            # final_snapshot() crops the state behind them — exactly
            # ntime - dev_rem steps, zero new programs (the AOT chunk
            # sizes never change) and zero new transfers
            steps_done = req.cfg.ntime - int(self.dev_rem[0])
            if steps_done < req.cfg.ntime:
                exit_mode = "steady"
                outer.steady_exits += 1
                with outer._lock:   # cross-thread with _cache_replay
                    outer.steps_saved_total += req.cfg.ntime - steps_done
                if self.tracer.enabled:
                    self.tracer.instant(
                        "steady-exit", self.lane_tracks[0],
                        trace_id=req.trace_id,
                        args={"id": req.id, "at_step": steps_done,
                              "requested": req.cfg.ntime,
                              "saved": req.cfg.ntime - steps_done,
                              "predicted_at_step": req.predicted_steps})
        self._trace_occupancy(0, req, "retired")
        rec = outer._finish_timing(req, chunks=int(self.lane_chunks[0]),
                                   steps_done=steps_done,
                                   exit_mode=exit_mode)
        snap = self.eng.final_snapshot()
        if sync:
            T = MegaLaneEngine.extract(snap)
            outer._writeback_job(rec, req, self.writer, lambda: T)
        else:
            outer._writeback_job(rec, req, self.writer,
                                 lambda: MegaLaneEngine.extract(snap))
        self._release()

    def process_boundary(self) -> None:
        """Take one boundary: fetch the OLDEST in-flight handle, judge,
        refill — the group runner's shape, with the chunk span on the
        mega lane's own process row carrying the halo-exchange geometry
        (fused-exchange count and ghost width) a timeline reader needs
        to see where the mesh fenced."""
        if self.inflight:
            seq, handle, predicted, snap, t_disp, k = self.inflight.popleft()
            b = self._fetch(handle)
            t_done = wall_clock()
            rem, finite = b[0], b[1]
            if self.tracer.enabled:
                kf = self.eng.kf if self.eng is not None else 0
                self.tracer.complete(
                    f"mega chunk {seq} ({k} steps)", self.group_track,
                    t_disp, t_done, cat="chunk",
                    args={"seq": seq, "k": k, "halo_width": kf,
                          "exchanges": -(-k // kf) if kf else 0})
            outer = self.outer
            if outer.prof.enabled:
                base = (t_disp if self.last_fetch_t is None
                        else max(self.last_fetch_t, t_disp))
                outer.prof.observe_chunk(self.cost_label, 1, self.depth,
                                         k, t_done - base,
                                         kernel=self.kernel,
                                         placement="mega")
                self.last_fetch_t = t_done
                warn = outer.prof.maybe_sample_memory(t_done)
                if warn is not None:
                    outer._mem_warn(warn)
            if not self.inflight:
                self.idle_from = t_done
            if not np.array_equal(rem, predicted):
                raise RuntimeError(
                    f"serve dispatch-ahead desync for mega lane "
                    f"{self.slot}: device remaining {rem.tolist()} != "
                    f"host-predicted {predicted.tolist()} at chunk {seq} "
                    f"— the mega countdown contract broke; results "
                    f"cannot be trusted")
            if outer.numerics is not None:
                self._ingest_numerics(seq, b)
            self._judge(seq, rem, finite, snap, sync=False)
            outer._note_boundary()
        else:
            self._judge(self.seq, self.dev_rem, None, None, sync=False)
        self._fill()

    # --- synchronous fallback (--dispatch-depth off) ----------------------
    def sync_round(self) -> None:
        outer = self.outer
        finite = None
        snap = None
        rem_vec = self.dev_rem
        req = self.occupant[0]
        if req is not None and int(self.dev_rem[0]) > 0:
            if outer._has_lane_faults:
                self._maybe_poison()
            k = min(self.chunk, int(self.dev_rem[0]))
            t0 = wall_clock()
            if self.idle_from is not None:
                outer.device_idle_s += t0 - self.idle_from
                if self.tracer.enabled:
                    self.tracer.complete("device-idle", self.group_track,
                                         self.idle_from, t0, cat="idle")
            with self.tracer.span("heat.sched.dispatch", self.group_track,
                                  cat="dispatch",
                                  args={"seq": self.seq, "k": k}):
                handle = self.eng.dispatch_chunk(k)
            b = self._fetch(handle)
            rem_vec, finite = b[0], b[1]
            outer.chunks_dispatched += 1
            self.idle_from = wall_clock()
            if self.tracer.enabled:
                self.tracer.complete(
                    f"mega chunk {self.seq} ({k} steps, fenced)",
                    self.group_track, t0, self.idle_from, cat="chunk",
                    args={"seq": self.seq, "k": k,
                          "halo_width": self.eng.kf})
            if outer.prof.enabled:
                outer.prof.observe_chunk(self.cost_label, 1, 0, k,
                                         self.idle_from - t0,
                                         kernel=self.kernel,
                                         placement="mega")
                warn = outer.prof.maybe_sample_memory(self.idle_from)
                if warn is not None:
                    outer._mem_warn(warn)
            self.lane_chunks[0] += 1
            self.dev_rem[0] = int(self.dev_rem[0]) - k
            if self.rollback:
                snap = self.eng.snapshot_state()
            if outer.numerics is not None:
                self._ingest_numerics(self.seq, b)
            outer._note_boundary()
        self._judge(self.seq, rem_vec, finite, snap, sync=True)
        self.seq += 1
        self._fill()

    def run_sync(self) -> None:
        while self.has_work():
            self.sync_round()
            self.outer._ckpt_tick()


class Engine:
    """Request-driven batched execution engine (library API).

    >>> eng = Engine(ServeConfig(lanes=4, chunk=8, buckets=(64,)))
    >>> rid = eng.submit(HeatConfig(n=32, ntime=100, dtype="float64"))
    >>> records = eng.results()   # drains the queue, returns all records

    ``submit`` only enqueues; ``run``/``results`` executes every admitted
    request to completion via dispatch-ahead continuous batching and
    returns the records in submit order.
    """

    def __init__(self, scfg: Optional[ServeConfig] = None):
        # default resolved per call (ruff B008: a call in a default is
        # evaluated once at definition — harmless for a frozen dataclass,
        # but the pattern is banned uniformly so the one day it guards a
        # mutable default it actually fires)
        scfg = scfg if scfg is not None else ServeConfig()
        self.scfg = scfg
        # request-scoped tracing + always-on flight recorder
        # (runtime/trace.py): every request mints a trace id at submit,
        # every layer appends spans to this bounded ring, and the ring is
        # dumped on watchdog/quarantine/crash — or exported to
        # ``scfg.trace`` at drain. ``trace_buffer=0`` disables recording
        # (ids are still minted: the record schema never flickers).
        self.tracer = trace_mod.Tracer(capacity=scfg.trace_buffer)
        # performance & cost observatory (runtime/prof.py): chunk-cost
        # model, per-tenant usage ledger, memory watermarks, SLO burn
        # monitor — all fed from timestamps this scheduler already takes.
        # Its locks are its own and are only ever taken AFTER (or
        # without) the engine lock, never before it — the gateway's
        # scrape endpoints can therefore never deadlock the hot path.
        targets = dict(SLO_TARGETS)
        targets.update((c, float(t)) for c, t in scfg.slo_targets)
        self.prof = prof_mod.Observatory(
            enabled=scfg.prof, slo_targets=targets,
            mem_poll_every=scfg.mem_poll_every,
            slo_fast_window_s=scfg.slo_fast_window_s,
            slo_slow_window_s=scfg.slo_slow_window_s,
            slo_burn_threshold=scfg.slo_burn_threshold)
        # numerics observatory (runtime/numerics.py, ISSUE 15): solution-
        # quality detectors fed from the stats rows of every fetched
        # boundary. Same lock contract as prof: its lock is its own and
        # only ever taken AFTER (or without) the engine lock, so gateway
        # scrape threads reading snapshot() cannot deadlock the hot path.
        self.numerics = (numerics_mod.NumericsObservatory(
            steady_tol=scfg.steady_tol) if scfg.numerics else None)
        self._queues: Dict[BucketKey, object] = {}  # policy queues
        # second placement tier (ISSUE 10): the engine-wide mega-lane
        # admission queue (same policy object as the bucket queues) plus
        # per-ndim mesh cache; lazily built so packed-only engines never
        # touch the mesh layer
        self._mega_queue = None
        self._mega_meshes: Dict[int, object] = {}
        self._mega_lanes_resolved: Optional[int] = None
        self.mega_compiles = 0    # mega chunk/seed/crop programs built
        self._records: List[dict] = []
        self._by_id: Dict[str, dict] = {}
        self._seq = 0
        # one engine-wide lock: records are mutated and emitted from both
        # the scheduler thread and the SnapshotWriter thread — JSON lines
        # must not interleave mid-line and record mutation must not race.
        # The same lock guards every policy-queue push/pop (the gateway's
        # HTTP threads submit while the scheduler thread pops) and backs
        # the condition the online loop + wait() callers sleep on.
        # Created through runtime/debug.make_lock so HEAT_TPU_LOCKCHECK=1
        # arms the engine<observatory order watchdog on this exact lock.
        self._lock = debug_mod.make_lock("engine")
        self._cond = threading.Condition(self._lock)
        self._listeners: List[Callable[[dict], None]] = []
        # online mode (serve/gateway.py): a background scheduler thread
        # drains continuously; submit() feeds it while lanes run
        self._thread: Optional[threading.Thread] = None
        self._draining = False
        self.loop_error: Optional[BaseException] = None
        # SLO/admission observability: who is queued (per-tenant depth
        # counters back the --tenant-quota check AND the /metrics queue
        # gauge), which request was admitted when (the policy's observable
        # output — the fifo regression test locks this trace), per-class
        # end-to-end latency + queue-depth-at-submit histograms
        self._queued_by_tenant: collections.Counter = collections.Counter()
        self.admission_trace: List[str] = []
        self.lat_hist: Dict[str, policy_mod.Histogram] = {}
        self.depth_hist = policy_mod.Histogram(policy_mod.DEPTH_BUCKETS)
        self.lane_grows = 0          # online lane-tier growth events
        # one compiled-program cache for the engine's lifetime: repeated
        # runs (a long-lived server draining wave after wave) never pay a
        # second (bucket, lane-tier) compile
        self._compiled: Dict = {}
        self.step_compiles = 0    # steady stepping programs built (the
                                  # criterion: at most one per
                                  # (bucket, lane-tier))
        self.tail_compiles = 0    # tail programs built (at most one per
                                  # (bucket, lane-tier), lazily)
        self.compile_s = 0.0
        # dispatch-ahead observability (summary()/cmd_serve surface these)
        self.chunks_dispatched = 0
        self.tail_chunks = 0
        self.boundary_waits = 0
        self.boundary_wait_s = 0.0   # host wall blocked on boundary fetches
        self.device_idle_s = 0.0     # est. device idle: per-group gaps with
                                     # nothing in flight at a boundary
        self.timing = None           # runtime.timing.Timing of the last run
        # lane-kernel observability (ISSUE 9): how many (bucket, tier)
        # groups wanted Pallas and got the XLA fallback (summary(),
        # /metrics gauge heat_tpu_serve_lane_kernel_fallbacks_total)
        self.lane_kernel_fallbacks = 0
        self._lane_fb_seen: set = set()
        # per-lane fault-domain observability (ISSUE 5)
        self.lanes_quarantined = 0   # requests failed nonfinite
        self.rollbacks = 0           # per-lane restore-and-re-step events
        self.deadline_misses = 0     # requests preempted/shed past deadline
        self._cancel_reqs: set = set()  # deadline-preemption by id
                                     # (cancel(): hedged-dispatch loser
                                     # cancel, POST /v1/cancel) — judged
                                     # at the same chunk-boundary sites
                                     # as deadline expiry
        # semantic scheduling (ISSUE 16): until=steady early retirements
        # and the device steps they did NOT run (the effective-throughput
        # multiplier the steady lab gates; /metrics + usage ledger bill
        # saved work as saved)
        self.steady_exits = 0
        self.steps_saved_total = 0
        self.shed = 0                # submits rejected by --max-queue
        self.watchdog_fired = 0      # boundary-fetch watchdog timeouts
        # zero-downtime serving (ISSUE 17): engine-state checkpointing.
        # The cadence clock counts PROCESSED chunk boundaries across all
        # runners; crossing the interval arms _ckpt_pause (runners stop
        # feeding the pipeline), and the driving loop takes the manifest
        # at the first empty-pipeline cut (_ckpt_tick). All mutated on
        # the scheduler thread under the engine lock; the gateway's
        # /drainz?handoff=1 thread flips _ckpt_pause/_handoff under the
        # same lock, and its scrape threads read _engine_ckpt_gen there.
        self.serve_resumed_total = 0  # requests re-admitted by --resume
        self.boundaries_total = 0     # processed chunk boundaries (the
                                      # checkpoint cadence clock and the
                                      # engine-kill@N fault address)
        self._engine_ckpt_gen = 0     # last PUBLISHED manifest generation
        self._engine_ckpt_next = 0    # next generation to write (0 =
                                      # scan the directory first; resume
                                      # seeds loaded generation + 1)
        self._last_ckpt_boundary = 0  # cadence clock at the last publish
        self._ckpt_pause = False      # armed: drain to the empty cut
        self._handoff = False         # drain-to-checkpoint requested
        self._active_runners = ()     # the driving loop's live runners
        self._active_writer = None    # ... and its SnapshotWriter
                                      # (both thread-confined to the
                                      # scheduler thread that set them)
        # engine-scoped fault plan (scfg.inject / HEAT_TPU_FAULTS); None on
        # every normal run — the hot loop then does no fault work at all
        self._plan = faults.plan_for(scfg)
        # two-level solve cache (ISSUE 19): consulted at submit (the one
        # admission door), fed by the writer thread's result publishes
        # and by chunk-boundary engine-checkpoint snapshots. None when
        # --cache off — every call site skips on one is-not-None test,
        # so the cache-off engine is behavior-identical to pre-cache
        # builds (regression-locked).
        self.solvecache = None
        if scfg.cache:
            from pathlib import Path as _Path

            cache_dir = scfg.cache_dir or (
                str(_Path(scfg.out_dir) / "solve-cache") if scfg.out_dir
                else "solve-cache")
            self.solvecache = solvecache_mod.SolveCache(
                cache_dir, max_bytes=scfg.cache_max_bytes,
                plan=self._plan)
        self._has_lane_faults = False  # flips on when a poisoned request
                                       # is admitted (gates _maybe_poison)
        self._fetch_seq = 0            # boundary-fetch counter (fetch-hang
                                       # @N addressing)
        # the gateway's canary prober (serve/probe.py), attached by
        # cmd_serve before any thread starts; None when not armed —
        # /metrics and /statusz read its stats() through this reference
        self.prober = None
        # race sanitizer (no-op unless HEAT_TPU_RACECHECK): exempt fields
        # the committed guard map sanctions as benign — the idempotent
        # mega-lane memo (allow-marked) and the typed object refs
        debug_mod.instrument_races(
            self, label="Engine",
            exempt=frozenset({"_mega_lanes_resolved", "tracer", "prof",
                              "numerics", "scfg", "prober",
                              "solvecache"}))

    # --- mega-lane placement (ISSUE 10) -----------------------------------
    @property
    def mega_lanes(self) -> int:
        """Resolved concurrent-mega-lane budget: the configured value, or
        the auto default (1 when this host has more than one device, 0
        on single-device hosts where overflow stays a rejection).
        Resolved lazily and once — the first overflow admission, summary
        or /metrics render pins it."""
        if self._mega_lanes_resolved is None:
            # heat-tpu: allow[races] idempotent memo — every thread computes the same deterministic value from immutable config, and the publish is one GIL-atomic store; first-writer-wins needs no lock
            self._mega_lanes_resolved = (
                self.scfg.mega_lanes if self.scfg.mega_lanes is not None
                else (1 if mega_device_count() > 1 else 0))
        return self._mega_lanes_resolved

    def _mega_shape(self, ndim: int) -> tuple:
        """The mesh shape a mega-lane of this rank would span (built
        meshes win; the auto factorization otherwise)."""
        mesh = self._mega_meshes.get(ndim)
        if mesh is not None:
            return tuple(mesh.devices.shape)
        from ..parallel.mesh import auto_mesh_shape

        return auto_mesh_shape(mega_device_count(), ndim)

    def _mega_mesh(self, ndim: int):
        mesh = self._mega_meshes.get(ndim)
        if mesh is None:
            from ..parallel.mesh import build_mesh

            mesh = self._mega_meshes[ndim] = build_mesh(ndim, None)
        return mesh

    def _mega_overflow_reason(self, cfg: HeatConfig):
        """``(reason, hint)`` when a bucket-overflow request can NOT run
        as a mega-lane (the enriched rejection record, with the mesh
        capacity ceiling and — when flipping one knob would serve it —
        a machine-readable hint); ``(None, None)`` when it can."""
        biggest = max(self.scfg.buckets)
        base = (f"bucket-overflow: request side {cfg.n} exceeds the "
                f"biggest bucket {biggest}")
        ndev = mega_device_count()
        if self.mega_lanes <= 0:
            shape = "x".join(map(str, self._mega_shape(cfg.ndim)))
            why = ("auto enables mega-lanes only on multi-device hosts"
                   if ndev <= 1 and self.scfg.mega_lanes is None
                   else "--mega-lanes 0")
            return (base + f"; mega-lane placement is off ({why}) though "
                    f"this host's {ndev}-device {shape} mesh could serve "
                    f"it", "enable --mega-lanes")
        shape = self._mega_shape(cfg.ndim)
        bad = [int(s) for s in shape if cfg.n % int(s)]
        if bad:
            return (base + f"; side {cfg.n} does not divide evenly over "
                    f"the {'x'.join(map(str, shape))} device mesh "
                    f"(mega-lane shard constraint) — resubmit at a side "
                    f"divisible by {max(int(s) for s in shape)}", None)
        return None, None

    def _note_mega_compile(self, k: int, seconds: float) -> None:
        """Compile accounting for the mega tier (chunk programs, and the
        k=0 seed/crop pair), kept out of the packed tier's
        one-per-(bucket, tier) step/tail counters."""
        self.mega_compiles += 1
        self.compile_s += seconds
        if self.tracer.enabled:
            t1 = wall_clock()
            self.tracer.complete(f"mega compile k={k}",
                                 self.tracer.thread_track("compiler"),
                                 t1 - seconds, t1, cat="compile",
                                 args={"k": k,
                                       "seconds": round(seconds, 4)})

    def _note_compile(self, k: int, seconds: float) -> None:
        if k == self.scfg.chunk:
            self.step_compiles += 1
        else:
            self.tail_compiles += 1
        self.compile_s += seconds
        if self.tracer.enabled:
            # compile-observatory span: the lazy tail/tier compile is the
            # one that lands mid-drain — make its wall visible on the
            # timeline, not just in the aggregate counter
            t1 = wall_clock()
            self.tracer.complete(f"compile k={k}",
                                 self.tracer.thread_track("compiler"),
                                 t1 - seconds, t1, cat="compile",
                                 args={"k": k,
                                       "seconds": round(seconds, 4)})

    # --- admission --------------------------------------------------------
    def submit(self, cfg: HeatConfig, request_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None,
               slo_class: Optional[str] = None,
               until: Optional[str] = None,
               tol: Optional[float] = None,
               _restore: Optional[dict] = None) -> str:
        """Admit one request; returns its id. Unservable requests become
        status='rejected' records instead of raising (see module doc).
        ``deadline_ms`` (request JSONL field of the same name) bounds the
        request's wall time from submission; it overrides the engine
        default ``ServeConfig.deadline_ms``. ``tenant``/``slo_class``
        (JSONL/HTTP fields ``tenant``/``class``) drive the fair-share and
        EDF admission policies; ``until``/``tol`` pick the completion
        semantics (``until="steady"`` retires the lane once its residual
        EWMA passes ``tol`` — default the engine ``--steady-tol`` — with
        ``ntime`` as the hard cap); malformed values raise (the
        JSONL/HTTP front doors pre-validate them into per-request
        rejections).

        ``_restore`` (serve/resume.py only) re-admits a request recovered
        from an engine-state checkpoint: ``{}`` for one that was still
        queued, or a payload with the checkpointed field/remaining/usage
        partials for one that was mid-solve — the admitting lane fill
        continues it at its last boundary, bit-identically.

        Thread-safe: the gateway's HTTP handler threads call this while
        the online scheduler thread is mid-drain — shared state mutates
        under the engine lock and the scheduler is woken per submit."""
        tenant, slo_class = validate_slo_fields(tenant, slo_class)
        until, tol = validate_until_fields(until, tol)
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        deadline_ms = (deadline_ms if deadline_ms is not None
                       else self.scfg.deadline_ms)
        # predictive layer (runtime/convergence.py): an until=steady
        # request gets a closed-form eigenmode ETA at admission — zero
        # observations needed — feeding EDF predicted-finish ordering,
        # the fair-share work estimate, and the predicted-vs-actual
        # retirement instant on the trace
        predicted = None
        if until == "steady":
            eff_tol = tol if tol is not None else self.scfg.steady_tol
            predicted = conv_mod.predict_admission_steps(cfg, eff_tol)
        shed_reason = None
        with self._lock:
            seq = self._seq
            rid = request_id or f"req-{seq:04d}"
            self._seq += 1
            if rid in self._by_id:
                raise ValueError(f"duplicate request id {rid!r}")
            trace_id = self.tracer.mint_trace_id()
            rec = {"id": rid, "n": cfg.n, "ndim": cfg.ndim,
                   "ntime": cfg.ntime, "dtype": cfg.dtype, "bc": cfg.bc,
                   "tenant": tenant, "class": slo_class, "status": "queued",
                   "placement": None,
                   "bucket": None, "lane": None, "queue_wait_s": None,
                   "solve_s": None, "steps_per_s": None, "error": None,
                   "deadline_ms": deadline_ms, "trace_id": trace_id,
                   "until": until, "steps_done": None, "exit": None,
                   "predicted_steps": predicted, "predicted_wall_s": None,
                   "resumed": _restore is not None, "cached": False,
                   "_submit_t": wall_clock()}
            if _restore is not None:
                # usage partials from the checkpointed incarnation: the
                # terminal stamp folds them in (no double billing — the
                # step count spans both incarnations by construction)
                self.serve_resumed_total += 1
                rec["_resumed_lane_s"] = float(_restore.get("lane_s")
                                               or 0.0)
            self._records.append(rec)
            self._by_id[rid] = rec
        if self.tracer.enabled:
            # flow start: the submitting thread (gateway handler, JSONL
            # loader, library caller) anchors the request's cross-thread
            # arrow; admission/retirement/terminal-record hops follow
            self.tracer.flow("s", self.tracer.thread_track(), trace_id,
                             ts=rec["_submit_t"])
        if cfg.bc == "periodic":
            self._reject(rec, "unsupported-bc: periodic has no padded-lane "
                              "form (wraparound would wrap at the bucket "
                              "edge, not the request edge)")
            return rid
        b = _bucket_for(cfg, self.scfg.buckets)
        key = None
        placement = "packed"
        if b is None:
            # two-tier placement (ISSUE 10): bucket overflow falls
            # through to the mega-lane admission queue — one request
            # spanning the whole device mesh — instead of a rejection,
            # wherever mega-lanes are on and the side shards evenly
            reason, hint = self._mega_overflow_reason(cfg)
            if reason is not None:
                self._reject(rec, reason, hint=hint)
                return rid
            placement = "mega"
        else:
            key = BucketKey(ndim=cfg.ndim, n=b, dtype=cfg.dtype, bc=cfg.bc)
        if predicted is not None and self.prof.enabled:
            rec["predicted_wall_s"] = self._forecast_wall(cfg, b, predicted)
        # solve cache consult (ISSUE 19) — at the admission door, after
        # every rejection gate, before a lane or queue slot is taken.
        # Only fixed-step requests CONSUME the cache (an until=steady
        # request's exit step is not knowable from the key — it only
        # populates, under its actual frontier); keys are physics-only,
        # so tenant/class/deadline/id never split entries. Checkpoint
        # re-admissions (_restore) already carry their own field.
        prefix_restore = None
        if (self.solvecache is not None and _restore is None
                and until == "steps"):
            hit = self.solvecache.lookup(cfg)
            if hit is not None and hit["kind"] == "full":
                if self._cache_replay(rec, cfg, b, placement, hit):
                    return rid
            elif hit is not None:
                prefix_restore = self._cache_prefix(rec, cfg, hit)
        with self._cond:
            queued = (sum(len(q) for q in self._queues.values())
                      + (len(self._mega_queue) if self._mega_queue else 0))
            if self.scfg.max_queue and queued >= self.scfg.max_queue:
                self.shed += 1
                shed_reason = (f"overloaded: admission queue full "
                               f"({queued} queued >= --max-queue "
                               f"{self.scfg.max_queue}); resubmit later")
            elif (self.scfg.tenant_quota
                  and self._queued_by_tenant[tenant]
                  >= self.scfg.tenant_quota):
                self.shed += 1
                shed_reason = (f"overloaded: tenant {tenant!r} holds "
                               f"{self._queued_by_tenant[tenant]} queued "
                               f"request(s) >= its --tenant-quota "
                               f"{self.scfg.tenant_quota}; resubmit later")
            else:
                rec["bucket"] = b
                rec["placement"] = placement
                submit_t = rec["_submit_t"]
                if placement == "mega":
                    q = self._mega_queue
                    if q is None:
                        q = self._mega_queue = policy_mod.make_queue(
                            self.scfg.policy, self.scfg.tenant_weights)
                else:
                    q = self._queues.get(key)
                    if q is None:
                        q = self._queues[key] = policy_mod.make_queue(
                            self.scfg.policy, self.scfg.tenant_weights)
                req = Request(
                    id=rid, cfg=cfg, submit_t=submit_t, key=key,
                    placement=placement,
                    deadline_t=(submit_t + deadline_ms / 1e3
                                if deadline_ms is not None else None),
                    tenant=tenant, slo_class=slo_class, seq=seq,
                    trace_id=trace_id, until=until, tol=tol,
                    predicted_steps=predicted,
                    restore=(_restore if _restore else prefix_restore))
                q.push(req)
                if self.tracer.enabled:
                    policy_mod.note_enqueue(self.tracer, self.scfg.policy,
                                            req)
                self._queued_by_tenant[tenant] += 1
                self.depth_hist.observe(float(queued + 1))
                self._cond.notify_all()   # wake the online scheduler
        if shed_reason is not None:
            self._reject(rec, shed_reason)
        return rid

    def _cache_replay(self, rec: dict, cfg: HeatConfig,
                      bucket: Optional[int], placement: str,
                      hit: dict) -> bool:
        """Full cache hit at the admission door: replay the stored npz
        through the normal record/listener path without ever occupying
        a lane — zero chunk programs dispatch, and an out-dir publish is
        a byte copy of the cached artifact (byte-identical to the
        cold-miss npz by construction). Billed as cached: zero
        lane_s/steps, the whole ``ntime`` credited as steps_saved, so
        the hit reconciles across records/ledger//v1/usage like every
        other terminal stamp. Returns False when the entry vanished
        mid-replay (eviction race) — the caller proceeds as a miss."""
        scfg = self.scfg
        path: Optional[str] = None
        T = None
        try:
            nbytes = int(hit["nbytes"])
            if scfg.out_dir:
                p = self.solvecache.replay(hit["path"], scfg.out_dir,
                                           rec["id"])
                path = str(p)
                nbytes = p.stat().st_size
            if scfg.keep_fields or not scfg.out_dir:
                T, _ = solvecache_mod.SolveCache.load(hit["path"])
        except Exception as e:  # noqa: BLE001 — entry evicted under us
            master_print(f"solve cache: replay of {hit['path']} failed "
                         f"({type(e).__name__}: {e}) — recomputing")
            return False
        now = wall_clock()
        with self._lock:
            rec["bucket"] = bucket
            rec["placement"] = placement
            rec["status"] = "ok"
            rec["cached"] = True
            rec["exit"] = "cached"
            rec["queue_wait_s"] = round(now - rec["_submit_t"], 6)
            rec["solve_s"] = 0.0
            rec["steps_per_s"] = None
            rec["steps_done"] = int(cfg.ntime)
            if path is not None:
                rec["path"] = path
            if T is not None:
                rec["T"] = T
            rec["usage"] = {"lane_s": 0.0, "steps": 0, "chunks": 0,
                            "bytes_written": int(nbytes),
                            "steps_saved": int(cfg.ntime),
                            "cached": True}
            self.steps_saved_total += int(cfg.ntime)
        if self.tracer.enabled:
            self.tracer.instant("cache-hit", self.tracer.thread_track(),
                                trace_id=rec["trace_id"],
                                args={"id": rec["id"],
                                      "step": int(hit["step"])})
        self._emit(rec)
        return True

    def _cache_prefix(self, rec: dict, cfg: HeatConfig,
                      hit: dict) -> Optional[dict]:
        """Prefix hit: seed the admitting lane fill from the cached
        field at ``hit['step']`` so the engine steps only the delta.
        The returned payload is the engine-checkpoint resume shape the
        lane fills already consume (``_fill``/mega ``_fill``);
        ``_cache_prefix_steps`` on the record makes the terminal stamp
        bill only the stepped delta, crediting the prefix as
        steps_saved. Returns None when the entry vanished under us —
        the request just runs from the IC."""
        try:
            T, step = solvecache_mod.SolveCache.load(hit["path"])
        except Exception as e:  # noqa: BLE001 — entry evicted under us
            master_print(f"solve cache: prefix read of {hit['path']} "
                         f"failed ({type(e).__name__}: {e}) — "
                         f"recomputing from the IC")
            return None
        remaining = int(cfg.ntime) - int(step)
        if remaining <= 0:
            return None
        with self._lock:
            rec["_cache_prefix_steps"] = int(step)
        if self.tracer.enabled:
            self.tracer.instant("cache-prefix",
                                self.tracer.thread_track(),
                                trace_id=rec["trace_id"],
                                args={"id": rec["id"],
                                      "step": int(step),
                                      "delta": remaining})
        return {"T": T, "remaining": remaining, "chunks": 0}

    def _forecast_wall(self, cfg: HeatConfig, b: Optional[int],
                       steps: int) -> Optional[float]:
        """Cost-model wall forecast for an ``until=steady`` admission,
        keyed on PREDICTED rather than nominal steps (runtime/prof.py).
        Best effort by design: None until the model has observed this
        geometry, and the lane tier is assumed saturated at ``--lanes``
        (the steady state of a loaded server)."""
        d = self.scfg.dispatch_depth
        depth = max(1, d) if d > 0 else 0
        if b is None:
            est = self.prof.cost.estimate_request_s(
                f"{cfg.ndim}d/n{cfg.n}/{cfg.dtype}/{cfg.bc}", 1, depth,
                steps, kernel="sharded", placement="mega")
            return None if est is None else round(est, 6)
        bucket = f"{cfg.ndim}d/n{b}/{cfg.dtype}/{cfg.bc}"
        for kernel in ("pallas", "xla"):
            est = self.prof.cost.estimate_request_s(
                bucket, self.scfg.lanes, depth, steps, kernel=kernel)
            if est is not None:
                return round(est, 6)
        return None

    def _lane_nan_steps(self, req: Request) -> List[int]:
        """Poison thresholds for one admitted request: the union of its
        own plan's and the engine plan's applicable lane-nan steps (the
        two can be the SAME cached plan object — dedupe by identity so a
        shared spec doesn't double-fire)."""
        plans = {id(p): p for p in (faults.plan_for(req.cfg), self._plan)
                 if p is not None}
        steps: set = set()
        for p in plans.values():
            steps.update(p.lane_nan_steps(req.id))
        return sorted(steps)

    def _lane_perturb_events(self, req: Request) -> List[tuple]:
        """Perturb ``(step, eps)`` events for one admitted request — the
        ``_lane_nan_steps`` contract for the numerics-observatory chaos
        channel (same identity-dedupe of a shared plan object)."""
        plans = {id(p): p for p in (faults.plan_for(req.cfg), self._plan)
                 if p is not None}
        events: set = set()
        for p in plans.values():
            events.update(p.perturb_events(req.id))
        return sorted(events)

    def _note_numerics_event(self, runner, lane: int, req: Request,
                             rem_at: int, ev: dict) -> None:
        """One numerics-observatory verdict (runtime/numerics.py event
        dict) becomes policy here: structured record, trace instant,
        flight dump, and — for violations under ``--numerics-guard
        quarantine`` — the runner's quarantine exit. Called from the
        scheduler thread off the boundary fetch, never while holding the
        engine lock (only the quarantine branch takes it, inside
        ``_fail_request``)."""
        done = req.cfg.ntime - rem_at
        if ev["kind"] == "steady":
            json_record("steady_state", id=req.id, lane=lane,
                        steps_done=done, remaining=rem_at,
                        resid=ev["resid"], resid_ewma=ev["resid_ewma"],
                        steady_tol=ev["steady_tol"],
                        trace_id=req.trace_id)
            if self.tracer.enabled:
                self.tracer.instant("steady-state",
                                    runner.lane_tracks[lane],
                                    trace_id=req.trace_id,
                                    args={"id": req.id, "at_step": done})
            if req.until == "steady":
                # semantic scheduling (ISSUE 16): ACT on the detector —
                # flag the lane for frontier retirement; the judge pass
                # of this same process_boundary call consumes the flag
                # (_ingest_numerics runs first, same epoch guard), and
                # _fill backfills the freed lane immediately after
                runner.steady_exit[lane] = rem_at
            return
        why = ev["why"]
        master_print(
            f"serve numerics: request {req.id} (lane {lane}) violated "
            f"the {why} detector at ~step {done} of {req.cfg.ntime} "
            f"(guard: {self.scfg.numerics_guard}) — see "
            f"TROUBLESHOOTING.md")
        json_record("numerics_violation", id=req.id, lane=lane, why=why,
                    steps_done=done, guard=self.scfg.numerics_guard,
                    tmin=ev.get("tmin"), tmax=ev.get("tmax"),
                    lo=ev.get("lo"), hi=ev.get("hi"), tol=ev.get("tol"),
                    heat=ev.get("heat"), heat_prev=ev.get("heat_prev"),
                    dheat=ev.get("dheat"),
                    dheat_ewma=ev.get("dheat_ewma"),
                    trace_id=req.trace_id)
        if self.tracer.enabled:
            self.tracer.instant("numerics-violation",
                                runner.lane_tracks[lane],
                                trace_id=req.trace_id,
                                args={"id": req.id, "why": why,
                                      "at_step": done})
        # flight-recorder trigger: an un-physical field is exactly the
        # postmortem case — the ring holds the lane's whole chunk/residual
        # history up to the escape
        self._flight_dump(f"numerics violation ({why}) on request "
                          f"{req.id}")
        if self.scfg.numerics_guard == "quarantine":
            runner._quarantine_numerics(lane, req, rem_at, why)

    def _reject(self, rec: dict, reason: str,
                hint: Optional[str] = None) -> None:
        with self._lock:
            rec["status"] = "rejected"
            rec["error"] = reason
            if hint is not None:
                # machine-readable remedy (ISSUE 10: an overflow a mesh
                # could have served names the knob that would serve it)
                rec["hint"] = hint
            rec["usage"] = prof_mod.empty_usage()   # schema-stable stamp
        self._emit(rec)

    def _fail_request(self, req: Request, status: str, reason: str,
                      lane: Optional[int] = None, steps_done: int = 0,
                      chunks: int = 0) -> None:
        """Fail ONE request with a structured status (nonfinite /
        deadline / error) — the per-lane fault-domain exit: the record
        carries the reason, the engine keeps serving everyone else.
        ``steps_done``/``chunks`` are the usage-ledger stamp: work the
        failed request DID consume (a preempted lane still occupied the
        group for its chunks — billing that work is the point of the
        per-tenant ledger)."""
        rec = self._by_id[req.id]
        now = wall_clock()
        with self._lock:
            self._cancel_reqs.discard(req.id)
            start = rec.pop("_start_t", None)
            base = rec.pop("_resumed_lane_s", 0.0)
            if start is not None:
                rec["solve_s"] = round(now - start + base, 6)
            elif base:
                rec["solve_s"] = round(base, 6)
            if rec["queue_wait_s"] is None:
                rec["queue_wait_s"] = round(now - req.submit_t, 6)
            if lane is not None:
                rec["lane"] = lane
            rec["status"] = status
            rec["error"] = reason
            rec["steps_done"] = int(steps_done)
            # a cache-prefix admission never ran its prefix steps: bill
            # only the stepped delta, credit the prefix as saved
            prefix = int(rec.pop("_cache_prefix_steps", 0) or 0)
            rec["usage"] = {"lane_s": rec["solve_s"] or 0.0,
                            "steps": max(0, int(steps_done) - prefix),
                            "chunks": int(chunks),
                            "bytes_written": 0, "steps_saved": prefix,
                            "cached": False}
        if self.numerics is not None:
            self.numerics.forget(req.id)   # terminal: drop detector state
        self._emit(rec)

    def _note_lane_fallback(self, key: BucketKey, lanes: int,
                            reason: str) -> None:
        """One (bucket, tier) wanted the Pallas lane program and got the
        XLA oracle instead: degrade LOUDLY — a human line, a structured
        ``lane_kernel_fallback`` record, the summary counter, and the
        /metrics gauge — but never an error (results are bit-identical
        by the oracle contract; only throughput differs). Deduped per
        (bucket, tier) so warm re-runs of the same group don't spam."""
        bucket = f"{key.ndim}d/n{key.n}/{key.dtype}/{key.bc}"
        with self._lock:
            if (key, lanes) in self._lane_fb_seen:
                return
            self._lane_fb_seen.add((key, lanes))
            self.lane_kernel_fallbacks += 1
        master_print(
            f"serve lane-kernel: bucket {bucket} tier {lanes} fell back "
            f"to the XLA lane program ({reason}); results identical, "
            f"throughput reduced — see TROUBLESHOOTING.md")
        json_record("lane_kernel_fallback", bucket=bucket, lanes=lanes,
                    requested=self.scfg.lane_kernel, reason=reason)
        if self.tracer.enabled:
            self.tracer.instant("lane-kernel-fallback",
                                self.tracer.thread_track("scheduler"),
                                args={"bucket": bucket, "lanes": lanes,
                                      "reason": reason})

    def _mem_warn(self, warn: dict) -> None:
        """The leak sentinel fired (runtime/prof.py MemWatermark): one
        structured ``mem_watermark`` record + a human line. Called from
        the scheduler thread at a chunk boundary — never inside the
        dispatch loop."""
        master_print(
            f"mem watermark: device memory grew monotonically by "
            f"{warn['growth_bytes'] / 2**20:.1f} MiB over the last "
            f"{warn['window_samples']} samples to "
            f"{warn['bytes_in_use'] / 2**20:.1f} MiB "
            f"({warn['source']}) — a rollback-stack or lane-grow leak "
            f"looks exactly like this; see TROUBLESHOOTING.md")
        json_record("mem_watermark", **warn)

    def _fail_group(self, runner: "_GroupRunner", exc: BaseException) -> None:
        """The boundary-fetch watchdog fired for one bucket group: its
        device state is unreadable (a wedged fetch means every newer
        chunk is suspect too), so every in-flight occupant and every
        still-queued request of THIS group fails with a structured
        record — and the other groups keep draining. This is the
        fail-clean alternative to `heat-tpu serve` hanging forever on
        one dead fetch. (The online loop reuses it as the generic
        fail-everything exit when the scheduler loop itself dies — only
        a real watchdog timeout bumps the watchdog counter.)"""
        is_watchdog = isinstance(exc, async_io.BoundedFetchTimeout)
        if is_watchdog:
            self.watchdog_fired += 1
            if self.tracer.enabled:
                self.tracer.instant("watchdog-fired", runner.group_track,
                                    args={"bucket": runner.track_name,
                                          "error": str(exc)})
        master_print(f"serve fetch watchdog: bucket {runner.key} boundary "
                     f"fetch hung ({exc}); failing the group's "
                     f"{sum(o is not None for o in runner.occupant)} "
                     f"in-flight and {len(runner.q)} queued request(s)")
        for lane, req in enumerate(runner.occupant):
            if req is not None:
                runner._trace_occupancy(lane, req, "error")
                self._fail_request(
                    req, "error",
                    f"fetch-watchdog: {exc} — lane {lane}'s group state "
                    f"is unreadable; request failed cleanly", lane=lane,
                    steps_done=max(0, req.cfg.ntime
                                   - int(runner.dev_rem[lane])),
                    chunks=int(runner.lane_chunks[lane]))
                runner.occupant[lane] = None
        while True:
            with self._lock:
                req = runner.q.pop()
                if req is not None:
                    self._queued_by_tenant[req.tenant] -= 1
            if req is None:
                break
            self._fail_request(
                req, "error",
                f"fetch-watchdog: {exc} — request was still queued when "
                f"its bucket group's boundary fetch hung")
        runner.inflight.clear()
        if is_watchdog:
            # flight-recorder trigger: the ring holds the wedged
            # request's whole span chain up to the hang — dump it next to
            # the results so the postmortem starts with a timeline
            self._flight_dump(f"fetch watchdog fired for bucket "
                              f"{runner.key}")

    def _flight_dump(self, reason: str) -> None:
        """Flight-recorder dump (watchdog fire / quarantine-after-
        rollbacks / scheduler crash): atomic write of the event ring to
        ``flight_dir`` (default: ``out_dir``; with neither set the dump
        is SKIPPED — never the cwd, which is how 81 stray trace files
        once landed at a repo root). Must never raise into the failure
        path it is documenting. A successful dump additionally emits a
        structured ``flightrec`` record naming the file — operators find
        the dump from the log stream, not by grepping the filesystem —
        and bumps the ``heat_tpu_flightrec_dumps_total`` counter
        (/metrics)."""
        d = self.scfg.flight_dir or self.scfg.out_dir
        if d is None:
            return
        try:
            path = self.tracer.flight_dump(d, reason)
        except Exception as e:  # noqa: BLE001 — best-effort by contract
            master_print(f"flight recorder: dump failed "
                         f"({type(e).__name__}: {e})")
            return
        if path is not None:
            json_record("flightrec", reason=reason, path=str(path),
                        events=len(self.tracer), dump=self.tracer.dumps,
                        max_dumps=trace_mod.MAX_FLIGHT_DUMPS)

    @staticmethod
    def _public(rec: dict) -> dict:
        """A record as callers see it: no field payload (``T`` can be a
        multi-MiB array — poll it explicitly via results()/records), no
        internal ``_``-prefixed bookkeeping."""
        return {k: v for k, v in rec.items()
                if k != "T" and not k.startswith("_")}

    def _emit(self, rec: dict) -> None:
        """Emit one request record: a JSON line (when enabled), the
        per-class latency histogram observation, a condition broadcast
        for ``wait()`` callers, and every registered listener. Called
        from the scheduler thread (rejections) AND the writer thread
        (finishes); the lock keeps concurrent lines from interleaving
        mid-line and snapshots the record fields consistently. Every
        emission is a terminal transition — records are only ever
        emitted once their status can no longer change."""
        now = wall_clock()
        with self._cond:
            snap = self._public(rec)
            listeners = list(self._listeners)
            submit_t = rec.get("_submit_t")
            if submit_t is not None and snap.get("status") != "rejected":
                cls = snap.get("class", "standard")
                h = self.lat_hist.get(cls)
                if h is None:
                    h = self.lat_hist[cls] = policy_mod.Histogram()
                h.observe(max(0.0, now - submit_t))
            # observatory feed: usage ledger + SLO burn windows consume
            # the terminal snapshot (their own locks — engine->prof lock
            # order only); an slo_alert payload is emitted OUTSIDE this
            # lock, like the listeners
            alert = self.prof.note_terminal(snap, now)
            if self.scfg.emit_records:
                # heat-tpu: allow[lock-discipline] the engine lock IS the
                # serialization point: record lines must not interleave
                json_record("serve_request", **snap)
            self._cond.notify_all()
        if alert is not None:
            master_print(
                f"slo alert: class {alert['class']!r} burning its error "
                f"budget at {alert['fast_burn']:.1f}x (fast) / "
                f"{alert['slow_burn']:.1f}x (slow) the sustainable rate "
                f"(target {alert['target']:g}) — see TROUBLESHOOTING.md")
            json_record("slo_alert", **alert)
        if self.tracer.enabled:
            # flow end: the terminal record left the engine (scheduler
            # thread for rejections/failures, writer thread for finishes)
            xid = snap.get("trace_id")
            if xid:
                self.tracer.flow("f", self.tracer.thread_track(), xid,
                                 ts=now)
        # listeners run OUTSIDE the lock: they may call poll()/summary()
        for fn in listeners:
            try:
                fn(snap)
            except Exception:  # noqa: BLE001 — a broken listener must not
                pass           # fail the request it is being told about

    # --- deadline preemption by id (cancel) --------------------------------
    def cancel(self, request_id: str) -> bool:
        """Deadline-preemption by request id — the hedged-dispatch loser
        cancel (fleet router) and ``POST /v1/cancel``. An unknown or
        already-terminal id answers False; otherwise the id is marked
        and the next chunk-boundary deadline judge preempts it with the
        same status ``deadline`` machinery an expired budget uses (a
        queued request is shed at pop). The lane is freed at its next
        boundary — cancellation is cooperative, never mid-chunk."""
        with self._lock:
            rec = self._by_id.get(request_id)
            if rec is None or rec["status"] in TERMINAL_STATUSES:
                return False
            self._cancel_reqs.add(request_id)
            self._cond.notify_all()
        return True

    def _deadline_cut(self, req: Request, now: float) -> Optional[str]:
        """``"expired" | "cancelled" | None`` — the one deadline verdict
        every chunk-boundary judge asks. The unlocked emptiness test
        keeps the no-cancellations hot path free of lock traffic; the
        membership read is re-taken under the lock."""
        if req.deadline_t is not None and now > req.deadline_t:
            return "expired"
        # benign emptiness peek: the set object is created once in
        # __init__ and only mutated (never rebound) under the engine
        # lock; a stale empty read just defers the cut one boundary,
        # and the locked re-check below is authoritative
        if not self._cancel_reqs:
            return None
        with self._lock:
            if req.id in self._cancel_reqs:
                return "cancelled"
        return None

    # --- incremental consumption (poll / wait / listeners) ----------------
    def poll(self, request_id: str) -> Optional[dict]:
        """Snapshot one request's record right now (``None`` — unknown
        id). Unlike ``results()`` this never blocks and never drains:
        the gateway's ``GET /v1/requests/<id>`` and any library caller
        can watch a request finish while the engine keeps running."""
        with self._lock:
            rec = self._by_id.get(request_id)
            return None if rec is None else self._public(rec)

    def field_of(self, request_id: str) -> Optional[np.ndarray]:
        """The final field of a terminal ``ok`` request, or ``None`` —
        from the in-memory record (``keep_fields`` / no out_dir) or the
        published ``.npz``. The gateway's ``GET /v1/requests/<id>?field=1``
        uses this so the canary prober (serve/probe.py) can verify the
        returned solution through the same front door clients use; the
        npz load runs outside the engine lock."""
        with self._lock:
            rec = self._by_id.get(request_id)
            T = rec.get("T") if rec is not None else None
            path = rec.get("path") if rec is not None else None
        if T is not None:
            return np.asarray(T)
        if path is not None:
            with np.load(path) as z:
                return np.asarray(z["T"])
        return None

    def wait(self, request_id: str, timeout: Optional[float] = None
             ) -> Optional[dict]:
        """Block until a request's record is terminal; returns the record
        snapshot, or ``None`` on timeout. Raises KeyError for an unknown
        id (a typo must not wait forever)."""
        deadline = (wall_clock() + timeout) if timeout is not None else None
        with self._cond:
            while True:
                rec = self._by_id.get(request_id)
                if rec is None:
                    raise KeyError(f"unknown request id {request_id!r}")
                if rec["status"] in TERMINAL_STATUSES:
                    return self._public(rec)
                remaining = (None if deadline is None
                             else deadline - wall_clock())
                if remaining is not None and remaining <= 0:
                    return None
                self._cond.wait(remaining if remaining is not None else 0.5)

    def add_listener(self, fn: Callable[[dict], None]) -> None:
        """Register a results-ready callback: ``fn(record_snapshot)``
        fires once per request at its terminal transition — the moment
        its lane retires (or it is rejected/failed), not at drain. May be
        called from the scheduler or writer thread; keep it quick."""
        with self._lock:
            self._listeners.append(fn)

    def remove_listener(self, fn: Callable[[dict], None]) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    def queue_depths(self) -> Dict[str, int]:
        """Queued (not yet admitted) request count per tenant."""
        with self._lock:
            return {t: n for t, n in self._queued_by_tenant.items() if n}

    def backlog_snapshot(self) -> Dict[str, int]:
        """Queued/running work totals for fleet placement (the
        ``GET /v1/status`` control endpoint): integer step sums a router
        converts to predicted backlog seconds via the cost-model rows it
        already scrapes. Reads records only — never runner state, which
        is scheduler-thread-confined — so any HTTP handler thread may
        call this under the engine lock. ``running_steps_bound`` counts
        each resident request at its full ``ntime`` (an upper bound: the
        device-side remaining count is not mirrored here), which is the
        conservative side for load balancing."""
        queued_req = queued_steps = running_req = running_steps = 0
        with self._lock:
            for rec in self._by_id.values():
                st = rec.get("status")
                if st == "queued":
                    queued_req += 1
                    queued_steps += int(rec.get("ntime") or 0)
                elif st == "running":
                    running_req += 1
                    running_steps += int(rec.get("ntime") or 0)
        return {"queued_requests": queued_req,
                "queued_steps": queued_steps,
                "running_requests": running_req,
                "running_steps_bound": running_steps}

    # --- engine-state checkpointing (ISSUE 17) ----------------------------
    def engine_ckpt_dir(self) -> str:
        """Resolved manifest directory: explicit --engine-ckpt-dir, else
        <out_dir>/engine-ckpt, else ./engine-ckpt."""
        from pathlib import Path

        if self.scfg.engine_ckpt_dir:
            return self.scfg.engine_ckpt_dir
        if self.scfg.out_dir:
            return str(Path(self.scfg.out_dir) / "engine-ckpt")
        return "engine-ckpt"

    def _note_boundary(self) -> None:
        """One processed chunk boundary (every runner calls this from the
        scheduler thread): advance the checkpoint cadence clock, arm the
        checkpoint pause when the interval is crossed, and give
        ``engine-kill@N`` its boundary address."""
        with self._lock:
            self.boundaries_total += 1
            n = self.boundaries_total
            interval = self.scfg.engine_ckpt_interval
            if (interval > 0 and not self._ckpt_pause
                    and n - self._last_ckpt_boundary >= interval):
                self._ckpt_pause = True
        if self._plan is not None:
            self._plan.maybe_engine_kill(n)

    def _ckpt_tick(self) -> None:
        """Take the armed checkpoint once the pipeline is EMPTY: every
        runner's in-flight deque drained, so the live device state is
        exactly the last judged boundary (the ``maybe_grow`` transplant
        precedent — the consistent cut). Called once per scheduler round
        by the driving loops; a no-op unless the pause is armed. A
        handoff arms the same pause but takes its own checkpoint at the
        cut, so the tick stands aside for it: both flags are read under
        the lock ``begin_drain`` sets them under, or a handoff landing
        mid-round would write an extra "interval" generation."""
        with self._cond:
            if not self._ckpt_pause or self._handoff:
                return
        runners = self._active_runners or ()
        if any(r.inflight for r in runners):
            return
        try:
            self._engine_checkpoint(reason="interval")
        finally:
            with self._cond:
                # a handoff requested meanwhile keeps dispatch paused
                self._ckpt_pause = self._handoff
                self._last_ckpt_boundary = self.boundaries_total
                self._cond.notify_all()

    def _engine_checkpoint(self, reason: str) -> None:
        """Snapshot the whole engine at THIS empty-pipeline cut: one
        on-device copy per occupied lane (D2H deferred to the writer
        thread), plus a JSON manifest of lane occupancy, queued requests
        in policy order, and usage partials. The manifest is submitted to
        the FIFO writer AFTER every field job and every earlier
        writeback, so a manifest on disk proves everything it references
        is durable — a kill mid-generation leaves fields without a
        manifest and discovery falls back one generation."""
        from pathlib import Path

        d = Path(self.engine_ckpt_dir())
        with self._lock:
            if self._engine_ckpt_next <= 0:
                self._engine_ckpt_next = ckpt_mod.next_engine_generation(d)
            gen = self._engine_ckpt_next
            self._engine_ckpt_next = gen + 1
        now = wall_clock()
        inflight_entries: List[dict] = []
        field_jobs: List = []
        failed: List[str] = []

        def _entry(req: Request, remaining: int, chunks: int,
                   lane_s: float, numerics) -> dict:
            rec = self._by_id[req.id]
            return {"id": req.id,
                    "cfg": dataclasses.asdict(req.cfg),
                    "fingerprint": ckpt_mod.config_fingerprint(req.cfg),
                    "placement": req.placement,
                    "remaining": int(remaining),
                    "steps_done": int(req.cfg.ntime - remaining),
                    "chunks": int(chunks),
                    "lane_s": round(float(lane_s), 6),
                    "until": req.until, "tol": req.tol,
                    "tenant": req.tenant, "class": req.slo_class,
                    "deadline_ms": rec.get("deadline_ms"),
                    "seq": req.seq,
                    "numerics": numerics}

        def _field_job(rid: str, fp: str, remaining: int, get_field,
                       cfg: Optional[HeatConfig] = None):
            def job():
                try:
                    T = get_field()
                    ckpt_mod.save_engine_field(d, gen, rid, T, fp,
                                               remaining)
                except BaseException as e:  # noqa: BLE001 — abort the gen
                    failed.append(f"{rid}: {type(e).__name__}: {e}")
                    return
                # chunk-boundary snapshots double as the solve cache's
                # prefix store (ISSUE 19): a later identical-physics
                # request seeds a lane from this cut and steps only the
                # delta. Best effort — put() swallows its own failures.
                if (self.solvecache is not None and cfg is not None
                        and remaining > 0):
                    step = int(cfg.ntime) - int(remaining)
                    if step > 0:
                        self.solvecache.put(cfg, step, T=T,
                                            kind="snapshot")
            job._trace = (f"engine-ckpt field {rid}", None)
            return job

        for r in (self._active_runners or ()):
            mega = isinstance(r, MegaLaneRunner)
            for lane, req in enumerate(r.occupant):
                if req is None:
                    continue
                remaining = int(r.dev_rem[lane])
                rec = self._by_id[req.id]
                lane_s = (now - rec.get("_start_t", now)
                          + rec.get("_resumed_lane_s", 0.0))
                num = (self.numerics.export_state(req.id)
                       if self.numerics is not None else None)
                e = _entry(req, remaining, int(r.lane_chunks[lane]),
                           lane_s, num)
                if mega:
                    snap = r.eng.final_snapshot()
                    get_field = (lambda s=snap:
                                 MegaLaneEngine.extract(s))
                else:
                    snap = r.eng.snapshot_lane(lane)
                    get_field = (lambda eng=r.eng, s=snap, n=req.cfg.n:
                                 eng.extract(s, n))
                inflight_entries.append(e)
                field_jobs.append(_field_job(req.id, e["fingerprint"],
                                             remaining, get_field,
                                             cfg=req.cfg))
        queued_entries: List[dict] = []
        with self._lock:
            queues = list(self._queues.values())
            if self._mega_queue is not None:
                queues.append(self._mega_queue)
            queued_reqs = [q2 for q in queues for q2 in q.items()]
        for req in sorted(queued_reqs, key=lambda q2: q2.seq):
            rst = req.restore
            if rst:
                # a resumed request still waiting for a lane carries its
                # checkpointed mid-solve field in host memory — persist
                # it as an in-flight entry or its progress would be lost
                e = _entry(req, int(rst["remaining"]),
                           int(rst.get("chunks", 0)),
                           float(rst.get("lane_s", 0.0)),
                           rst.get("numerics"))
                inflight_entries.append(e)
                field_jobs.append(_field_job(
                    req.id, e["fingerprint"], int(rst["remaining"]),
                    lambda rst=rst: rst["T"], cfg=req.cfg))
            else:
                e = _entry(req, req.cfg.ntime, 0, 0.0, None)
                e.pop("numerics")
                queued_entries.append(e)
        with self._lock:
            live = ({e["id"] for e in inflight_entries}
                    | {e["id"] for e in queued_entries})
            done = sorted(rid for rid in self._by_id if rid not in live)
        manifest = {"kind": ckpt_mod.ENGINE_MANIFEST_KIND,
                    "version": ckpt_mod.ENGINE_MANIFEST_VERSION,
                    "generation": gen, "reason": reason,
                    "boundaries": self.boundaries_total,
                    "policy": self.scfg.policy,
                    "inflight": inflight_entries,
                    "queued": queued_entries,
                    "done": done}

        def manifest_job():
            if failed:
                master_print(
                    f"engine checkpoint: generation {gen} ABORTED — "
                    f"{len(failed)} lane field(s) failed to persist "
                    f"({'; '.join(failed)}); the previous generation "
                    f"remains the resume point")
                return
            path = ckpt_mod.save_engine_manifest(d, gen, manifest,
                                                 plan=self._plan)
            with self._lock:
                self._engine_ckpt_gen = gen
            json_record("engine_ckpt", generation=gen, reason=reason,
                        path=str(path), boundaries=manifest["boundaries"],
                        inflight=len(inflight_entries),
                        queued=len(queued_entries), done=len(done))
        manifest_job._trace = (f"engine-ckpt manifest gen {gen}", None)

        writer = self._active_writer
        if writer is not None:
            for job in field_jobs:
                writer.submit(job)
            writer.submit(manifest_job)
        else:
            for job in field_jobs:
                job()
            manifest_job()

    # --- execution --------------------------------------------------------
    def run(self) -> List[dict]:
        """Drain every queued request through dispatch-ahead continuous
        batching; returns all records (submit order). Reentrant: new
        submits after a run are served by the next run against warm
        compiled programs."""
        from ..runtime.timing import Timing

        if self.online:
            raise RuntimeError(
                "Engine.run()/results() cannot be called while the online "
                "scheduler thread is serving — use poll()/wait() for "
                "records, shutdown() to drain")
        writer = async_io.SnapshotWriter(tracer=self.tracer)
        t0 = wall_clock()
        try:
            runners = [
                _GroupRunner(self, key, self._queues[key], writer)
                for key in list(self._queues) if self._queues[key]
            ]
            if (self._mega_queue and len(self._mega_queue)
                    and self.mega_lanes > 0):
                # one runner per occupied mega slot: round-robined with
                # the packed groups, so a mega boundary's bookkeeping
                # hides under packed compute and vice versa
                runners += [
                    MegaLaneRunner(self, i, self._mega_queue, writer)
                    for i in range(min(self.mega_lanes,
                                       len(self._mega_queue)))]
            # engine-state checkpointing reads the live runners + writer
            # from the driving loop (scheduler-thread-confined)
            self._active_runners = tuple(runners)
            self._active_writer = writer
            if self.scfg.dispatch_depth == 0:
                # synchronous debugging fallback: groups drain one at a
                # time with a fence at every boundary (the PR-3 shape)
                for r in runners:
                    try:
                        r.run_sync()
                    except async_io.BoundedFetchTimeout as e:
                        self._fail_group(r, e)
            else:
                live = [r for r in runners if r.has_work()]
                while live:
                    # an armed engine checkpoint fires at the empty cut,
                    # BEFORE the pipeline refills (see _ckpt_tick)
                    self._ckpt_tick()
                    # prime every group's device queue before anyone
                    # blocks: one group's boundary D2H + bookkeeping then
                    # hides under the other groups' queued compute
                    for r in live:
                        r.dispatch_fill()
                    nxt = []
                    for r in live:
                        try:
                            r.process_boundary()
                            r.dispatch_fill()  # refilled lanes step while
                                               # other groups take
                                               # boundaries
                        except async_io.BoundedFetchTimeout as e:
                            # the watchdog is a GROUP fault domain: fail
                            # this group's requests, keep draining the rest
                            self._fail_group(r, e)
                            continue
                        if r.has_work():
                            nxt.append(r)
                    live = nxt
        except BaseException as e:
            # flight-recorder trigger: the scheduler loop died — dump the
            # ring first (cheap, bounded), THEN drain: every writeback
            # already queued still lands (or fails per-request) — no
            # orphan *.tmp, no dropped result — but a writer error must
            # not mask the scheduler error already propagating
            self._flight_dump(f"scheduler crashed: {type(e).__name__}: {e}")
            writer.drain(raise_errors=False)
            self._active_runners, self._active_writer = (), None
            raise
        # always-at-drain checkpoint (engine_ckpt_interval > 0 opts in):
        # the batch's end state — every request done — becomes the newest
        # generation, so a later --resume re-admits nothing twice
        if self.scfg.engine_ckpt_interval > 0:
            self._engine_checkpoint(reason="drain")
        # normal exit: per-request jobs swallow their own failures, so a
        # surviving writer error here is a real bug and must surface
        writer.drain()
        self._active_runners, self._active_writer = (), None
        self._stamp_timing(Timing, wall_clock() - t0)
        if self.tracer.enabled:
            self.tracer.complete("engine.run", self.tracer.thread_track(),
                                 t0, cat="engine")
            if self.scfg.trace:
                self.tracer.export(self.scfg.trace)
        return list(self._records)

    def _stamp_timing(self, Timing, wall: float) -> None:
        mem = self.prof.mem.snapshot() if self.scfg.prof else {}
        num = self.numerics
        self.timing = Timing(total_s=wall, solve_s=wall,
                             compile_s=self.compile_s,
                             dispatch_depth=self.scfg.dispatch_depth,
                             serve_policy=self.scfg.policy,
                             boundary_wait_s=round(self.boundary_wait_s, 6),
                             lanes_quarantined=self.lanes_quarantined,
                             rollbacks=self.rollbacks,
                             deadline_misses=self.deadline_misses,
                             shed=self.shed,
                             mem_peak_bytes=mem.get("peak_bytes"),
                             steady_lanes=(num.steady_total
                                           if num is not None else None),
                             numerics_violations=(
                                 num.violation_total
                                 if num is not None else None))

    def results(self) -> List[dict]:
        """``run`` + records (the common library call)."""
        if (any(self._queues.values())
                or (self._mega_queue and len(self._mega_queue))):
            self.run()
        return list(self._records)

    # --- online mode (the gateway's engine shape) -------------------------
    @property
    def online(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    @property
    def draining(self) -> bool:
        return self._draining

    def start(self) -> "Engine":
        """Start the online scheduler thread: from here on ``submit()``
        feeds lanes *while they run* — requests arriving between chunk
        boundaries are admitted at the next one (the Orca iteration-level
        contract, now actually online). Idempotent while running."""
        # background-thread debug plumbing: uncaught crashes in the
        # scheduler/writer/handler threads become structured thread_crash
        # records, and the race sanitizer's record mode can flight-dump
        debug_mod.install_thread_excepthook()
        debug_mod.set_flight_dump_hook(self._flight_dump)
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._draining = False
            self.loop_error = None
            self._thread = threading.Thread(
                target=self._serve_loop, daemon=True,
                name="heat-tpu-serve-scheduler")
            self._thread.start()
        return self

    def begin_drain(self, handoff: bool = False) -> None:
        """Stop admission-by-policy: the online loop finishes every lane
        already admitted AND every request already queued, then exits.
        Callers gate *new* work themselves (the gateway 503s new solves
        the moment draining flips). ``handoff=True`` is drain-to-
        checkpoint (POST /drainz?handoff=1): the loop additionally stops
        lane fills and chunk dispatch, takes the in-flight boundaries
        already queued, checkpoints the whole engine at the first
        empty-pipeline cut — WITHOUT waiting for lanes to finish — and
        exits; ``serve --resume`` picks the work up where it stopped.
        Idempotent, and a later plain drain never cancels a requested
        handoff."""
        with self._cond:
            self._draining = True
            if handoff:
                self._handoff = True
                self._ckpt_pause = True
            self._cond.notify_all()

    def shutdown(self, timeout: Optional[float] = None) -> bool:
        """``begin_drain`` + join the scheduler thread. Returns True once
        the loop has exited (False = still draining after ``timeout``).
        Idempotent: safe to call repeatedly and without ``start()``."""
        self.begin_drain()
        t = self._thread
        if t is None:
            return True
        t.join(timeout)
        if t.is_alive():
            return False
        with self._lock:
            self._thread = None
        return True

    def _serve_loop(self) -> None:
        """The online scheduler: the same dispatch-ahead round-robin as
        ``run()``, but runners persist for the engine's lifetime, new
        bucket groups appear as their first request arrives, idle groups
        grow their lane tier when a burst outruns it, and an empty engine
        parks on the condition variable until a submit (or drain) wakes
        it. Exits when draining AND idle; the writer drains on every
        exit path so no accepted request's writeback is dropped."""
        from ..runtime.timing import Timing

        writer = async_io.SnapshotWriter(tracer=self.tracer)
        # bucket groups keyed by BucketKey; mega slots by ("mega-slot", i)
        runners: Dict[object, object] = {}
        self._active_writer = writer
        t0 = wall_clock()
        try:
            while True:
                if self._handoff:
                    # drain-to-checkpoint: no fills, no new dispatch —
                    # take only the boundaries already in flight, then
                    # checkpoint at the first empty cut and exit. Lane
                    # occupants stay status="running" (no terminal
                    # records); they and the queue ride the manifest.
                    self._active_runners = tuple(runners.values())
                    busy = [r for r in runners.values() if r.inflight]
                    for r in busy:
                        try:
                            r.process_boundary()
                        except async_io.BoundedFetchTimeout as e:
                            self._fail_group(r, e)
                    if not any(r.inflight for r in runners.values()):
                        self._engine_checkpoint(reason="handoff")
                        break
                    continue
                with self._lock:
                    keys = [k for k, q in self._queues.items() if q]
                for key in keys:
                    r = runners.get(key)
                    if r is None:
                        r = runners[key] = _GroupRunner(
                            self, key, self._queues[key], writer)
                        r.allow_growth = True
                    else:
                        r.maybe_grow()
                        r._fill()
                if (self._mega_queue and len(self._mega_queue)
                        and self.mega_lanes > 0):
                    # mega slots appear as their first overflow request
                    # arrives and persist for the engine's lifetime,
                    # like the bucket runners
                    for i in range(self.mega_lanes):
                        mkey = ("mega-slot", i)
                        mr = runners.get(mkey)
                        if mr is None:
                            runners[mkey] = MegaLaneRunner(
                                self, i, self._mega_queue, writer)
                        else:
                            mr._fill()
                self._active_runners = tuple(runners.values())
                self._ckpt_tick()
                live = [r for r in runners.values() if r.has_work()]
                if not live:
                    with self._cond:
                        if (self._draining
                                and not any(
                                    q for q in self._queues.values())
                                and not (self._mega_queue
                                         and len(self._mega_queue))):
                            break
                        # parked: a submit()/begin_drain() notify wakes us;
                        # the timeout only bounds lost-wakeup worst cases
                        self._cond.wait(0.05)
                    continue
                if self.scfg.dispatch_depth == 0:
                    for r in live:
                        try:
                            r.sync_round()
                        except async_io.BoundedFetchTimeout as e:
                            self._fail_group(r, e)
                else:
                    for r in live:
                        r.dispatch_fill()
                    for r in live:
                        try:
                            r.process_boundary()
                            r.dispatch_fill()
                        except async_io.BoundedFetchTimeout as e:
                            self._fail_group(r, e)
            # normal drain exit (the handoff exit checkpointed already,
            # pre-break): an interval-opted engine always leaves a final
            # generation at drain — the zero-downtime restart point
            if self.scfg.engine_ckpt_interval > 0 and not self._handoff:
                self._engine_checkpoint(reason="drain")
        except BaseException as e:  # noqa: BLE001 — surfaced via loop_error
            # a scheduler-loop crash in a daemon thread has nowhere to
            # propagate: record it (gateway /healthz + cmd_serve check it)
            # and fail every in-flight/queued request cleanly
            with self._lock:
                self.loop_error = e
            master_print(f"serve scheduler loop failed: "
                         f"{type(e).__name__}: {e}")
            self._flight_dump(f"scheduler loop crashed: "
                              f"{type(e).__name__}: {e}")
            for r in runners.values():
                self._fail_group(r, e)
        finally:
            try:
                writer.drain(raise_errors=False)
            finally:
                self._active_runners, self._active_writer = (), None
                self._stamp_timing(Timing, wall_clock() - t0)
                if self.tracer.enabled:
                    self.tracer.complete("serve-loop",
                                         self.tracer.thread_track(), t0,
                                         cat="engine")
                    if self.scfg.trace:
                        try:
                            self.tracer.export(self.scfg.trace)
                        except OSError as te:
                            master_print(f"trace export to "
                                         f"{self.scfg.trace} failed: {te}")
                with self._cond:
                    self._cond.notify_all()  # unblock wait() callers

    # --- lane retirement --------------------------------------------------
    def _finish_timing(self, req: Request, chunks: int = 0,
                       steps_done: Optional[int] = None,
                       exit_mode: str = "steps") -> dict:
        steps = int(req.cfg.ntime if steps_done is None else steps_done)
        rec = self._by_id[req.id]
        now = wall_clock()
        with self._lock:
            start = rec.pop("_start_t", now)
            # a resumed request's first incarnation billed lane seconds
            # too — fold the checkpointed partial in; steps_done already
            # spans both incarnations (ntime - final remaining)
            lane_s = (now - start) + rec.pop("_resumed_lane_s", 0.0)
            # a cache-prefix admission (ISSUE 19) seeded the lane at
            # _cache_prefix_steps: the lane only STEPPED the delta —
            # bill that, credit the prefix as steps_saved (riding the
            # same accounting as steady early exits)
            prefix = int(rec.pop("_cache_prefix_steps", 0) or 0)
            stepped = max(0, steps - prefix)
            rec["solve_s"] = round(lane_s, 6)
            rec["steps_per_s"] = (round(stepped / lane_s, 3)
                                  if lane_s > 0 else None)
            rec["steps_done"] = steps
            rec["exit"] = exit_mode
            # the usage-ledger stamp (runtime/prof.py): what THIS request
            # consumed — bytes_written is finalized by the writer thread
            # once the publish lands, before the record is emitted.
            # Semantic scheduling bills ACTUAL steps; the steps a steady
            # exit did not run (or a cache prefix made unnecessary) are
            # credited as steps_saved.
            rec["usage"] = {"lane_s": rec["solve_s"],
                            "steps": stepped,
                            "chunks": int(chunks), "bytes_written": 0,
                            "steps_saved": int(req.cfg.ntime) - stepped,
                            "cached": False}
            if prefix:
                self.steps_saved_total += prefix
        if self.numerics is not None:
            self.numerics.forget(req.id)   # terminal: drop detector state
        return rec

    def _writeback_job(self, rec: dict, req: Request,
                       writer: "async_io.SnapshotWriter",
                       get_field) -> None:
        """Build + submit the writer-thread job for one finished request.
        ``get_field()`` produces the host field — under dispatch-ahead it
        performs the snapshot D2H *in the writer thread*; the sync
        fallback passes a host array already fetched."""
        cfg, scfg = req.cfg, self.scfg
        attempts = {"n": 0}
        # captured before the job runs: _finish_timing already stamped the
        # actual step count (ntime, or the steady-exit frontier)
        steps_done = rec.get("steps_done")

        tr = self.tracer

        def job():
            # Runs in the writer thread, as one heat.lane.writeback span
            # (snapshot D2H + atomic publish, on the writer's own track).
            # Transient sink errors are re-raised so the SnapshotWriter's
            # bounded in-thread retry (backoff, same budget as checkpoints)
            # gets its shot; a final failure is recorded on THIS request
            # and swallowed — it must not poison writer._exc and kill the
            # other lanes' drain.
            attempts["n"] += 1
            with tr.span("heat.lane.writeback",
                         tr.thread_track("writer") if tr.enabled else None,
                         cat="io", args={"id": req.id},
                         trace_id=rec.get("trace_id")):
                try:
                    T = get_field()
                    plan = faults.plan_for(cfg)
                    if plan is not None:
                        plan.sink_fault(cfg.ntime)
                    path = (str(_write_result(scfg.out_dir, req.id, T, cfg,
                                              steps=steps_done))
                            if scfg.out_dir else None)
                    # bytes the tenant's result cost: the published file's
                    # size, or the in-memory field bytes when nothing hits
                    # disk — finalized HERE (writer thread) so the ledger add
                    # at emission sees the complete stamp
                    from pathlib import Path as _Path

                    nbytes = (_Path(path).stat().st_size if path is not None
                              else int(T.nbytes))
                    with self._lock:
                        if scfg.keep_fields or not scfg.out_dir:
                            rec["T"] = T
                        if path is not None:
                            rec["path"] = path
                        rec["status"] = "ok"
                        rec["usage"]["bytes_written"] = int(nbytes)
                    # solve-cache population, on the writer
                    # thread after the publish landed: a byte copy of the
                    # published artifact (or the identical serialization
                    # when nothing hit disk), keyed under the ACTUAL step
                    # count — a steady early exit caches under its exit
                    # frontier so later fixed-step requests can prefix-hit
                    # it. Best effort: put() swallows its own failures.
                    if self.solvecache is not None:
                        self.solvecache.put(
                            cfg, int(cfg.ntime if steps_done is None
                                     else steps_done),
                            T=T, src_path=path, kind="result")
                except BaseException as e:  # noqa: BLE001 — per-request record
                    if async_io.is_transient(e) and attempts["n"] <= writer.retries:
                        raise
                    with self._lock:
                        rec["status"] = "error"
                        rec["error"] = f"{type(e).__name__}: {e}"
            self._emit(rec)

        job._trace = None        # the job records its own span
        writer.submit(job)

    def _finish_async(self, eng: LaneEngine, lane: int, req: Request,
                      writer, chunks: int = 0,
                      steps_done: Optional[int] = None,
                      exit_mode: str = "steps") -> None:
        """Dispatch-ahead retirement: take a one-lane ON-DEVICE snapshot
        (enqueued behind the in-flight chunks; the scheduler thread never
        blocks) and move the D2H + writeback wholly into the writer."""
        rec = self._finish_timing(req, chunks=chunks, steps_done=steps_done,
                                  exit_mode=exit_mode)
        snap = eng.snapshot_lane(lane)
        n = req.cfg.n
        self._writeback_job(rec, req, writer, lambda: eng.extract(snap, n))

    def _finish_sync(self, eng: LaneEngine, lane: int, req: Request,
                     writer, chunks: int = 0,
                     steps_done: Optional[int] = None,
                     exit_mode: str = "steps") -> None:
        """Sync-fallback retirement: fetch the lane on the scheduler
        thread (fences every chunk in flight), write back in the writer."""
        rec = self._finish_timing(req, chunks=chunks, steps_done=steps_done,
                                  exit_mode=exit_mode)
        T = eng.extract_lane(lane, req.cfg.n)
        self._writeback_job(rec, req, writer, lambda: T)

    # --- reporting --------------------------------------------------------
    def summary(self) -> dict:
        with self._lock:
            by_status = collections.Counter(
                r["status"] for r in self._records)
            by_placement = collections.Counter(
                r["placement"] for r in self._records
                if r.get("placement"))
            n = len(self._records)
            queued = (sum(len(q) for q in self._queues.values())
                      + (len(self._mega_queue) if self._mega_queue else 0))
        # observatory snapshots AFTER the engine lock is released
        # (engine -> prof/numerics lock order; see Engine.__init__)
        obs = self.prof.summary(wall_clock())
        ns = (self.numerics.snapshot()
              if self.numerics is not None else None)
        return {"requests": n, **dict(by_status),
                "numerics": self.scfg.numerics,
                "numerics_guard": self.scfg.numerics_guard,
                "steady_lanes": ns["steady_total"] if ns else 0,
                "numerics_violations": (ns["violation_total"]
                                        if ns else 0),
                "prof": self.scfg.prof,
                "cost_model": obs["cost_model"],
                "mem": obs["mem"],
                "slo_burn": obs["slo_burn"],
                "flightrec_dumps": self.tracer.dumps,
                "policy": self.scfg.policy,
                "lane_kernel": self.scfg.lane_kernel,
                "lane_kernel_fallbacks": self.lane_kernel_fallbacks,
                "placement": dict(by_placement),
                "mega_lanes": self.mega_lanes,
                "mega_compiles": self.mega_compiles,
                "queued_now": queued,
                "lane_grows": self.lane_grows,
                "step_compiles": self.step_compiles,
                "tail_compiles": self.tail_compiles,
                "compile_s": round(self.compile_s, 3),
                "dispatch_depth": self.scfg.dispatch_depth,
                "chunks_dispatched": self.chunks_dispatched,
                "tail_chunks": self.tail_chunks,
                "boundary_waits": self.boundary_waits,
                "boundary_wait_s": round(self.boundary_wait_s, 6),
                "device_idle_s": round(self.device_idle_s, 6),
                "lanes_quarantined": self.lanes_quarantined,
                "rollbacks": self.rollbacks,
                "deadline_misses": self.deadline_misses,
                "steady_exits": self.steady_exits,
                "steps_saved": self.steps_saved_total,
                "serve_resumed": self.serve_resumed_total,
                "cache": (self.solvecache.stats()
                          if self.solvecache is not None else None),
                "engine_ckpt_interval": self.scfg.engine_ckpt_interval,
                "engine_ckpt_generation": self._engine_ckpt_gen,
                "shed": self.shed,
                "watchdog_fired": self.watchdog_fired}
