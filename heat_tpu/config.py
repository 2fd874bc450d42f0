"""Configuration: ``input.dat`` parsing and run options.

The reference drives every variant from a positional whitespace text file
``input.dat`` holding ``n sigma nu dom_len ntime`` (serial form, see
``fortran/serial/heat.f90:13``) with a sixth ``soln`` dump flag in the MPI
variants (``fortran/mpi+cuda/heat.F90:83``). Single-process variants silently
ignore a trailing sixth field, so one file drives every backend — this parser
preserves that contract (both arities accepted everywhere).

What the reference expresses as *compile-time* flags (``-DUSE_CUDA``,
``-DNO_AWARE`` in ``fortran/mpi+cuda/makefile:1-6``; ``SINGLE_PRECISION`` in
``fortran/hip/heat_kernel.cpp:5-9``) become *runtime* fields here: ``comm``
(direct vs host-staged halo exchange), ``dtype``, ``backend``.
"""

from __future__ import annotations

import dataclasses
import math
import re
from pathlib import Path
from typing import Optional, Tuple

_DTYPES = ("float64", "float32", "bfloat16")
_BACKENDS = ("serial", "xla", "pallas", "sharded")
_BCS = ("edges", "ghost", "periodic")
_ICS = ("hat", "hat_half", "hat_small", "uniform", "zero", "sine")
_COMMS = ("direct", "staged")
_ASYNC_IO = ("on", "off", "auto")
_ON_NAN = ("abort", "rollback")
_LOCAL_KERNELS = ("auto", "xla", "pallas")

# --serve-lane-kernel grammar (serve/scheduler.py ServeConfig.lane_kernel):
# the serving engine's chunk-program body per bucket. "auto" = the Pallas
# multi-lane kernels on TPU wherever the bucket has a kernel plan, the
# vmapped XLA stencil elsewhere; "pallas"/"xla" force it (an unavailable
# Pallas bucket under "pallas" degrades to XLA as a structured
# lane_kernel_fallback record + counter, never an error — the XLA lane
# program is the bit-exactness oracle either way).
LANE_KERNELS = ("auto", "pallas", "xla")


@dataclasses.dataclass(frozen=True)
class HeatConfig:
    """Full run configuration.

    The first six fields mirror ``input.dat`` exactly; the rest are framework
    options (runtime analogs of the reference's build-time variant choices).
    """

    # --- input.dat fields (fortran/serial/heat.f90:13, mpi+cuda/heat.F90:83)
    n: int = 256                # grid points per side
    sigma: float = 0.25         # CFL number
    nu: float = 0.05            # diffusivity
    dom_len: float = 2.0        # domain length
    ntime: int = 30             # number of timesteps
    soln: bool = False          # dump solution files at the end

    # --- framework options
    ndim: int = 2               # 2 -> 5-point stencil, 3 -> 7-point
    dtype: str = "float32"      # float64 parity / float32 / bfloat16(+f32 acc)
    backend: str = "xla"
    ic: str = "hat"             # initial condition preset (see grid.py)
    bc: str = "edges"           # "edges": frozen boundary cells (serial semantics)
                                # "ghost": Dirichlet-by-ghost ring (MPI semantics)
                                # "periodic": torus topology — the pbc=.true.
                                # the reference's mpi_cart_create is built for
                                # but never enables (mpi+cuda/heat.F90:76,97)
    bc_value: float = 1.0       # boundary temperature (unused for periodic)
    comm: str = "direct"        # halo exchange: direct ICI ppermute vs host-staged
    local_kernel: str = "auto"  # sharded per-shard compute: auto (pallas on
                                # TPU, xla elsewhere), or forced
    mesh_shape: Optional[Tuple[int, ...]] = None  # device mesh; None = auto
    heartbeat_every: int = 0    # print "time_it: i" every k steps (0 = off)
    write_int: bool = False     # dump the initial field to int.dat pre-solve
                                # (the single-process reference variants do
                                # this unconditionally,
                                # fortran/serial/heat.f90:50-55 — their
                                # presets below turn it on)
    report_sum: bool = False    # global temperature sum (the reference's
                                # commented-out MPI_Reduce, mpi+cuda/heat.F90:266-273)
    checkpoint_every: int = 0   # periodic snapshot interval (0 = off)
    checkpoint_dir: str = "checkpoints"
    async_io: str = "auto"      # checkpoint/numerics I/O pipeline: "on" =
                                # snapshot-and-continue (one device-side
                                # buffer copy at the boundary; D2H + disk
                                # write in a background thread, bounded
                                # queue), "off" = the reference-shaped
                                # sync path (device idles through fetch +
                                # write), "auto" = on (the hook for a
                                # future platform heuristic; see
                                # use_async_io)
    profile_dir: Optional[str] = None  # jax.profiler trace output dir
    check_numerics: bool = False  # per-chunk NaN/Inf detection (debug mode)
    on_nan: str = "abort"       # non-finite response under check_numerics:
                                # "abort" raises at the flagged step (the
                                # original contract); "rollback" restores
                                # the last boundary whose finite flag
                                # PASSED and re-steps — transient soft
                                # errors (or injected NaN) recover, while a
                                # deterministic blow-up re-flags at the
                                # same step and aborts after a bounded
                                # number of retries (backends/common.py)
    inject: str = ""            # deterministic fault-injection spec
                                # (runtime/faults.py grammar:
                                # "crash@N[:proc=P]", "nan@N",
                                # "ckpt-corrupt@N", "ckpt-truncate@N",
                                # "sink-error@N[:times=K]",
                                # "sink-slow:ms=M", comma-separated).
                                # Empty (the default) = no fault layer at
                                # all; HEAT_TPU_FAULTS env var is the
                                # worker-process channel
    fuse_steps: int = 0         # pallas temporal blocking: FTCS steps fused
                                # per kernel pass (0 = auto, 1 = off)
    parity_order: bool = False  # literal update-then-swap step ordering
                                # (mpi+cuda/heat.F90:209-218): sharded-only
                                # bit-parity mode carrying the ghost ring as
                                # state; see backends/sharded.py

    def __post_init__(self):
        if self.n < 3:
            raise ValueError(f"grid size n must be >= 3, got {self.n}")
        if self.ntime < 0:
            raise ValueError(f"ntime must be >= 0, got {self.ntime}")
        if self.ndim not in (2, 3):
            raise ValueError(f"ndim must be 2 or 3, got {self.ndim}")
        if self.dtype not in _DTYPES:
            raise ValueError(f"dtype must be one of {_DTYPES}, got {self.dtype!r}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, got {self.backend!r}")
        if self.bc not in _BCS:
            raise ValueError(f"bc must be one of {_BCS}, got {self.bc!r}")
        if self.ic not in _ICS:
            raise ValueError(f"ic must be one of {_ICS}, got {self.ic!r}")
        if self.comm not in _COMMS:
            raise ValueError(f"comm must be one of {_COMMS}, got {self.comm!r}")
        if self.local_kernel not in _LOCAL_KERNELS:
            raise ValueError(
                f"local_kernel must be one of {_LOCAL_KERNELS}, got {self.local_kernel!r}")
        # FTCS stability wants sigma <= 1/(2*ndim); allow mildly unstable
        # experiments but reject nonsense outright, in every dimension.
        if self.sigma <= 0 or self.sigma > 10:
            raise ValueError(f"sigma out of range: {self.sigma}")
        if self.fuse_steps < 0:
            raise ValueError(f"fuse_steps must be >= 0, got {self.fuse_steps}")
        if self.async_io not in _ASYNC_IO:
            raise ValueError(
                f"async_io must be one of {_ASYNC_IO}, got {self.async_io!r}")
        if self.on_nan not in _ON_NAN:
            raise ValueError(
                f"on_nan must be one of {_ON_NAN}, got {self.on_nan!r}")
        if self.on_nan == "rollback" and not self.check_numerics:
            raise ValueError(
                "on_nan='rollback' requires check_numerics=True — the "
                "finite flag at each boundary is the rollback trigger")
        if self.inject:
            # fail at parse time, not at step N of a long solve (lazy import:
            # the common inject="" path must not load the fault layer at all)
            from .runtime.faults import parse_spec

            parse_spec(self.inject)

    # --- derived quantities (fortran/serial/heat.f90:15-17,59) -------------
    @property
    def delta(self) -> float:
        """Grid spacing: dom_len / (n - 1)."""
        return self.dom_len / (self.n - 1)

    @property
    def dt(self) -> float:
        """Timestep from the CFL condition: sigma * delta^2 / nu."""
        return (self.sigma * self.delta**2) / self.nu

    @property
    def r(self) -> float:
        """Stencil coefficient nu*dt/delta^2.

        Algebraically identical to ``sigma`` (the dt substitution cancels);
        the reference still derives it through dt (fortran/serial/heat.f90:59)
        and so do we, keeping the full chain for config parity.
        """
        return (self.nu * self.dt) / self.delta**2

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.n,) * self.ndim

    @property
    def points(self) -> int:
        return self.n**self.ndim

    def use_async_io(self) -> bool:
        """Resolve the ``async_io`` knob to a verdict for this run.

        "auto" resolves to ON: the on-loop cost of the async pipeline is
        one device-side buffer copy per boundary (microseconds at HBM
        bandwidth) against the seconds-scale D2H+write it takes off the
        critical path, so there is no measured regime where sync wins.
        The tri-state exists so a platform heuristic can demote auto later
        without repurposing the explicit values: "off" stays the
        bit-faithful reference-shaped fallback (and the A/B baseline for
        benchmarks/ckpt_overlap.py), "on" stays a user promise. The serial
        backend ignores the knob (host-resident field — there is no D2H to
        hide)."""
        return self.async_io != "off"

    def with_(self, **kw) -> "HeatConfig":
        return dataclasses.replace(self, **kw)


def parse_input(path: str | Path) -> HeatConfig:
    """Parse an ``input.dat`` file (5- or 6-field form).

    Field order: ``n sigma nu dom_len ntime [soln]`` — README.md:7 and
    ``fortran/mpi+cuda/heat.F90:81-85``. Tokens may span multiple lines
    (Fortran list-directed reads don't care); extra trailing tokens beyond
    six are ignored, like the serial variant ignores the ``soln`` flag.
    """
    text = Path(path).read_text()
    toks = re.split(r"\s+", text.strip())
    if len(toks) < 5:
        raise ValueError(
            f"{path}: expected at least 5 fields 'n sigma nu dom_len ntime', got {toks}"
        )
    n = int(toks[0])
    sigma = float(toks[1])
    nu = float(toks[2])
    dom_len = float(toks[3])
    ntime = int(toks[4])
    soln = bool(int(toks[5])) if len(toks) >= 6 else False
    return HeatConfig(n=n, sigma=sigma, nu=nu, dom_len=dom_len, ntime=ntime, soln=soln)


# Request-JSONL surface of the serving engine (serve/api.py): the physics
# and per-request knobs a tenant may set. Framework-level execution knobs
# (backend, mesh, checkpointing, async_io) are engine policy, not request
# payload — a request naming them is a typo or a privilege confusion, and
# both must reject loudly rather than silently serve different physics.
_REQUEST_KEYS = ("n", "sigma", "nu", "dom_len", "ntime", "ndim", "dtype",
                 "ic", "bc", "bc_value", "inject")

# Request keys the SCHEDULER owns (never part of the physics config):
# "id" names the record, "deadline_ms" bounds the request's wall time from
# submission (overriding the engine-default --serve-deadline), "tenant"
# names the submitting tenant (fair-share accounting + per-tenant quotas),
# "class" picks the SLO class, and "until"/"tol" pick the completion
# semantics (fixed step count vs steady-state early exit) — see
# serve/scheduler.py + serve/policy.py.
_SCHEDULER_KEYS = ("id", "deadline_ms", "tenant", "class", "until", "tol")

# SLO classes of the serving front-end, name -> admission priority (lower
# is more urgent). The class is a *scheduler* field: it shapes admission
# order (serve/policy.py edf/fair policies) and labels the /metrics
# latency histograms; it never reaches the physics. Defined here because
# this module is the one validation chokepoint for request payloads —
# JSONL (serve/api.py) and HTTP (serve/gateway.py) both funnel through
# validate_slo_fields, so a typoed class can never silently serve at the
# wrong tier.
SLO_CLASSES = {"interactive": 0, "standard": 1, "batch": 2}
DEFAULT_SLO_CLASS = "standard"
DEFAULT_TENANT = "default"

# Default per-class SLO targets (deadline-hit fraction) for the burn-rate
# monitor (runtime/prof.py BurnMonitor): the error budget a class may
# spend is 1 - target, and the monitor's burn rate is miss_fraction /
# budget. Tighter classes get tighter budgets; override per engine with
# ``--slo-targets interactive=0.999`` (parse_slo_targets below). Lives
# here with SLO_CLASSES because this module is the one validation
# chokepoint for anything class-shaped.
SLO_TARGETS = {"interactive": 0.99, "standard": 0.95, "batch": 0.9}

_TENANT_RE = re.compile(r"^[A-Za-z0-9._-]{1,64}$")


def validate_slo_fields(tenant, slo_class) -> Tuple[str, str]:
    """Validate (and default) a request's tenant/class pair.

    Raised errors are per-request rejections at both front doors (JSONL
    parse, HTTP admission) — the same loud-typo contract as
    config_from_request's unknown-key check."""
    tenant = DEFAULT_TENANT if tenant is None else str(tenant)
    if not _TENANT_RE.match(tenant):
        raise ValueError(
            f"tenant must match {_TENANT_RE.pattern} (1-64 chars of "
            f"[A-Za-z0-9._-]), got {tenant!r}")
    slo_class = DEFAULT_SLO_CLASS if slo_class is None else str(slo_class)
    if slo_class not in SLO_CLASSES:
        raise ValueError(
            f"class must be one of {sorted(SLO_CLASSES)} (priority order "
            f"{sorted(SLO_CLASSES, key=SLO_CLASSES.get)}), got {slo_class!r}")
    return tenant, slo_class


# Completion semantics of a request (semantic scheduling, ISSUE 16):
# "steps" runs exactly ntime steps (the default, bit-for-bit the historic
# behavior); "steady" retires the lane at the first chunk boundary whose
# residual EWMA passes the steady tolerance (per-request "tol", else the
# engine-wide --steady-tol), with ntime as the hard cap. Defined here
# because this module is the one validation chokepoint for request
# payloads — JSONL (serve/api.py) and HTTP (serve/gateway.py) both funnel
# through validate_until_fields.
UNTIL_MODES = ("steps", "steady")
DEFAULT_UNTIL = "steps"


def validate_until_fields(until, tol) -> Tuple[str, Optional[float]]:
    """Validate (and default) a request's until/tol pair.

    ``tol`` is only meaningful with ``until=steady``; supplying it on a
    fixed-step request is rejected loudly (same loud-typo contract as
    validate_slo_fields — a tenant who set ``tol`` expected early exit,
    and silently running all steps would serve different semantics)."""
    until = DEFAULT_UNTIL if until is None else str(until)
    if until not in UNTIL_MODES:
        raise ValueError(
            f"until must be one of {list(UNTIL_MODES)}, got {until!r}")
    if tol is not None:
        if until != "steady":
            raise ValueError(
                f"tol is only valid with until='steady', got until={until!r}")
        try:
            tol = float(tol)
        except (TypeError, ValueError):
            raise ValueError(f"tol must be a positive number, got {tol!r}")
        if not (tol > 0.0) or not math.isfinite(tol):
            raise ValueError(f"tol must be a positive finite number, "
                             f"got {tol!r}")
    return until, tol


def parse_listen(s) -> Tuple[str, int]:
    """``--listen HOST:PORT`` grammar: ':0' / '0' pick an ephemeral port,
    a bare port listens on 127.0.0.1 (the gateway is a front-end, not an
    exposed-by-default service)."""
    text = str(s).strip()
    host, sep, port_s = text.rpartition(":")
    if not sep:
        host, port_s = "", text
    host = host or "127.0.0.1"
    try:
        port = int(port_s)
    except ValueError:
        raise ValueError(
            f"--listen must be HOST:PORT (port an integer), got {s!r}"
        ) from None
    if not 0 <= port <= 65535:
        raise ValueError(f"--listen port must be in [0, 65535], got {port}")
    return host, port


def parse_tenant_weights(s) -> Tuple[Tuple[str, float], ...]:
    """``--tenant-weights a=4,b=1`` -> (("a", 4.0), ("b", 1.0)). Unlisted
    tenants weigh 1.0 (serve/policy.py FairShareQueue)."""
    out = []
    for tok in str(s).split(","):
        tok = tok.strip()
        if not tok:
            continue
        name, sep, w = tok.partition("=")
        if not sep:
            raise ValueError(
                f"--tenant-weights entries must be NAME=WEIGHT, got {tok!r}")
        tenant, _ = validate_slo_fields(name.strip(), None)
        try:
            weight = float(w)
        except ValueError:
            raise ValueError(
                f"--tenant-weights weight must be a number, got {w!r}"
            ) from None
        if not weight > 0:
            raise ValueError(
                f"--tenant-weights weight must be > 0, got {weight}")
        out.append((tenant, weight))
    return tuple(out)


def parse_slo_targets(s) -> Tuple[Tuple[str, float], ...]:
    """``--slo-targets interactive=0.999,batch=0.8`` -> (("interactive",
    0.999), ("batch", 0.8)). Classes must exist (SLO_CLASSES) and targets
    lie strictly in (0, 1) — a target of 1.0 is a zero error budget and
    every burn rate would be infinite; unlisted classes keep the
    SLO_TARGETS defaults."""
    out = []
    for tok in str(s).split(","):
        tok = tok.strip()
        if not tok:
            continue
        name, sep, t = tok.partition("=")
        if not sep:
            raise ValueError(
                f"--slo-targets entries must be CLASS=TARGET, got {tok!r}")
        name = name.strip()
        if name not in SLO_CLASSES:
            raise ValueError(
                f"--slo-targets class must be one of {sorted(SLO_CLASSES)}, "
                f"got {name!r}")
        try:
            target = float(t)
        except ValueError:
            raise ValueError(
                f"--slo-targets target must be a number, got {t!r}"
            ) from None
        if not 0.0 < target < 1.0:
            raise ValueError(
                f"--slo-targets target must be in (0, 1), got {target}")
        out.append((name, target))
    return tuple(out)


def parse_on_off(v, flag: str) -> bool:
    """``on``/``off`` CLI grammar shared by boolean serve knobs
    (``--prof``)."""
    s = str(v).strip().lower()
    if s == "on":
        return True
    if s == "off":
        return False
    raise ValueError(f"{flag} must be 'on' or 'off', got {v!r}")


def parse_dispatch_depth(v) -> int:
    """``--dispatch-depth`` grammar (serve CLI): ``on`` -> 2 (the default
    pipeline: inspect chunk i's boundary while chunk i+1 computes),
    ``off`` -> 0 (fully synchronous debugging fallback — fence every
    boundary, extract on the scheduler thread), an integer N >= 1 -> keep
    N chunk programs in flight per bucket group. Deeper pipelines only
    help when boundary bookkeeping outlasts a whole chunk; each extra
    level delays lane swaps by one chunk, so 2 is almost always right."""
    s = str(v).strip().lower()
    if s == "on":
        return 2
    if s == "off":
        return 0
    try:
        n = int(s)
    except ValueError:
        raise ValueError(
            f"--dispatch-depth must be 'on', 'off', or an integer >= 1, "
            f"got {v!r}") from None
    if n < 1:
        raise ValueError(
            f"--dispatch-depth integer form must be >= 1 (use 'off' for "
            f"the synchronous fallback), got {n}")
    return n


def parse_mega_lanes(v) -> Optional[int]:
    """``--mega-lanes`` grammar (serve CLI): ``auto`` (default) -> None,
    resolved by the engine to 1 on a multi-device host and 0 on a
    single-device one; an integer N >= 0 pins the concurrent mega-lane
    budget (0 = bucket overflow stays a rejection, the pre-mega
    behavior, bit-identically)."""
    s = str(v).strip().lower()
    if s == "auto":
        return None
    try:
        n = int(s)
    except ValueError:
        raise ValueError(
            f"--mega-lanes must be 'auto' or an integer >= 0, got {v!r}"
        ) from None
    if n < 0:
        raise ValueError(f"--mega-lanes must be >= 0, got {n}")
    return n


def config_from_request(d) -> HeatConfig:
    """Build a HeatConfig from one parsed serve-request object.

    ``id`` and ``deadline_ms`` are the scheduler's (_SCHEDULER_KEYS),
    everything else must be a known request key; HeatConfig's own
    __post_init__ then validates values exactly as it does for the CLI,
    so a request cannot express a config the solo path would reject.
    """
    unknown = set(d) - set(_REQUEST_KEYS) - set(_SCHEDULER_KEYS)
    if unknown:
        raise ValueError(
            f"unknown request key(s) {sorted(unknown)}; allowed: "
            f"{sorted(_REQUEST_KEYS)} (+ optional {sorted(_SCHEDULER_KEYS)})")
    kw = {k: d[k] for k in _REQUEST_KEYS if k in d}
    # JSON numbers arrive untyped: pin the integer fields (a float n would
    # sail through range validation and break shapes much later)
    for k in ("n", "ntime", "ndim"):
        if k in kw:
            kw[k] = int(kw[k])
    for k in ("sigma", "nu", "dom_len", "bc_value"):
        if k in kw:
            kw[k] = float(kw[k])
    return HeatConfig(**kw)


def write_input(cfg: HeatConfig, path: str | Path) -> None:
    """Write the 6-field ``input.dat`` form (readable by every variant)."""
    # repr keeps full precision: a write/parse round-trip must not perturb
    # the physics (dt, r, checkpoint fingerprints).
    Path(path).write_text(
        f"{cfg.n} {cfg.sigma!r} {cfg.nu!r} {cfg.dom_len!r} {cfg.ntime} {int(cfg.soln)}\n"
    )


# Named presets reproducing each reference variant's semantics, so a user of
# the reference can select their variant by name (see SURVEY.md quirk #1: the
# IC/BC families differ silently between variants).
VARIANTS = {
    # Default-behavior parity (not just IC/BC): every Fortran single-process
    # variant writes int.dat unconditionally before solving
    # (fortran/serial/heat.f90:50-55, cuda_kernel/heat.F90:107-112,
    # cuda_cuf/heat.F90:94) and prints "time_it:" every step (serial :62,
    # cuda_kernel :31, cuda_cuf :29); the MPI variants heartbeat
    # master-gated without an int.dat (mpi+cuda/heat.F90:207,
    # hip/heat.F90:241); the python variants do neither. Opt out with
    # ``--no-write-int`` / ``--heartbeat-every 0``.
    #
    # fortran/serial/heat.f90: hat IC on [0.5,1.5]^2, frozen boundary cells
    "serial": dict(ic="hat", bc="edges", backend="serial", dtype="float64",
                   write_int=True, heartbeat_every=1),
    # fortran/cuda_kernel/heat.F90:99: hat with y in [0.5,1.0].
    # NOTE: f64 bit-parity implies the XLA step — the hand-written Pallas
    # kernel has no f64 (no f64 on the TPU VPU), so the pallas backend
    # transparently falls back. Run with --dtype float32 to exercise the
    # hand-written kernel itself (contract-tested in tests/test_config.py).
    "cuda_kernel": dict(ic="hat_half", bc="edges", backend="pallas", dtype="float64",
                        write_int=True, heartbeat_every=1),
    "cuda_managed": dict(ic="hat_half", bc="edges", backend="pallas", dtype="float64",
                         write_int=True, heartbeat_every=1),
    # fortran/cuda_cuf/heat.F90:86: same IC family, compiler-generated kernels
    "cuda_cuf": dict(ic="hat_half", bc="edges", backend="xla", dtype="float64",
                     write_int=True, heartbeat_every=1),
    # fortran/mpi+cuda/heat.F90:243-251: uniform 2.0, Dirichlet-by-ghost walls
    "mpi_cuda": dict(ic="uniform", bc="ghost", backend="sharded", comm="direct",
                     dtype="float64", heartbeat_every=1),
    # same but the staged (NO_AWARE) communication path, makefile:3-4
    "mpi_cuda_na": dict(ic="uniform", bc="ghost", backend="sharded", comm="staged",
                        dtype="float64", heartbeat_every=1),
    # fortran/hip/heat.F90: always-staged swap
    "hip": dict(ic="uniform", bc="ghost", backend="sharded", comm="staged",
                dtype="float64", heartbeat_every=1),
    # python/serial/heat.py: hat on [0.5,1.0]^2 w/ per-step edge reassert == edges BC
    # (no int.dat, no time_it heartbeat — the python variants print/plot only)
    "python_serial": dict(ic="hat_small", bc="edges", backend="serial", dtype="float64"),
    # python/cuda/cuda.py: throughput benchmark (IC no-op bug not replicated;
    # uniform field benchmarks identically)
    "python_cuda": dict(ic="uniform", bc="edges", backend="pallas", dtype="float32"),
}


def variant_config(name: str, base: Optional[HeatConfig] = None) -> HeatConfig:
    if name not in VARIANTS:
        raise KeyError(f"unknown variant {name!r}; choose from {sorted(VARIANTS)}")
    base = base or HeatConfig()
    return base.with_(**VARIANTS[name])
