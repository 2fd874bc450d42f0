"""Compile-time guard on the default sharded path (VERDICT r3 #2).

The auto fuse depth resolves to k*=32 at flagship 2D scale, the depth
whose compile stalled >25 min in round 3. These tests pin the guard's
policy: bounded probe of EVERY chunk size drive will compile, executable
hand-off (no double compile), loud job-wide k=16 fallback on timeout,
and — just as important — all the cases where the guard must stay out of
the way (explicit fuse_steps, shallow auto depths, budget 0, CPU)."""

import time

import pytest

from heat_tpu.backends import common, sharded
from heat_tpu.config import HeatConfig
from heat_tpu.parallel.mesh import build_mesh


def _flagship_cfg(**kw):
    # n=4096: the auto depth is 32 (narrow shard, chunk cap 32), which
    # is the class the guard still covers after round 5 capped wide
    # shards at k=16 and left depths <= 16 unguarded (the 16384^2
    # flagship's k=16 live compile is a bounded 471 s — see
    # _guard_fuse_compile)
    kw.setdefault("fuse_steps", 0)
    kw.setdefault("ntime", 500)
    kw.setdefault("dtype", "float32")
    return HeatConfig(n=4096, backend="sharded", mesh_shape=(1, 1), **kw)


@pytest.fixture
def mesh():
    return build_mesh(2, (1, 1))


def test_chunk_sizes_match_drive_warmup():
    # steady chunk + remainder: both are programs drive compiles, so both
    # are programs the guard must bound
    cfg = HeatConfig(n=64, ntime=1000, heartbeat_every=300)
    assert common.chunk_sizes(cfg, 1000) == [100, 300]
    assert common.chunk_sizes(cfg, 300) == [300]
    assert common.chunk_sizes(cfg, 0) == []
    assert common.chunk_sizes(HeatConfig(n=64, ntime=500), 500) == [500]


def test_bounded_compile_success_and_timeout():
    r, err = sharded._bounded_compile(lambda: 42, budget_s=5.0)
    assert (r, err) == (42, None)
    r, err = sharded._bounded_compile(lambda: time.sleep(30), budget_s=0.05)
    assert (r, err) == (None, "timeout")


def test_bounded_compile_propagates_exceptions():
    with pytest.raises(RuntimeError, match="boom"):
        sharded._bounded_compile(
            lambda: (_ for _ in ()).throw(RuntimeError("boom")), 5.0)


def test_agree_any_timeout_single_process_is_identity():
    assert sharded._agree_any_timeout(False) is False
    assert sharded._agree_any_timeout(True) is True


def test_guard_falls_back_on_compile_timeout(mesh, monkeypatch, capsys):
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "0.05")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    monkeypatch.setattr(sharded, "_compile_probe",
                        lambda *a, **kw: time.sleep(30))
    cfg = _flagship_cfg()
    assert sharded.fuse_depth_sharded(cfg, (1, 1)) == 32
    out, pre, rep = sharded._guard_fuse_compile(cfg, mesh, cfg.ntime)
    assert out.local_kernel == "xla" and pre is None
    # the probed depth is PINNED into the fallback: the xla kernel is
    # exempt from the chunk cap, so fuse_steps=0 could silently
    # recompute a different depth than the warning promises
    assert out.fuse_steps == 32
    assert rep.probe_s > 0  # the probe's wall cost is reported, not hidden
    assert rep.timed_out and rep.orphan == "left_running"  # thread probe
    assert rep.degraded == {"local_kernel": "xla", "fuse_steps": 32}
    msg = capsys.readouterr().out
    assert "WARNING" in msg and "local_kernel='xla'" in msg


def test_guard_falls_back_when_a_peer_timed_out(mesh, monkeypatch, capsys):
    """Job-wide agreement: even a LOCALLY successful probe must fall back
    if any peer's timed out — different fuse depths are different SPMD
    programs (mismatched collectives hang the job)."""
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "5")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    monkeypatch.setattr(sharded, "_compile_probe",
                        lambda *a, **kw: {500: object()})
    monkeypatch.setattr(sharded, "_agree_any_timeout", lambda t: True)
    out, pre, rep = sharded._guard_fuse_compile(_flagship_cfg(), mesh, 500)
    assert out.local_kernel == "xla" and pre is None
    assert rep.timed_out  # the agreed verdict, not the local outcome


def test_guard_hands_probe_executables_forward(mesh, monkeypatch):
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "5")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    fake = {500: object()}
    calls = []

    def probe(cfg, mesh, kf, remaining, padded):
        calls.append((kf, remaining, padded))
        return fake

    monkeypatch.setattr(sharded, "_compile_probe", probe)
    out, pre, rep = sharded._guard_fuse_compile(_flagship_cfg(), mesh, 500)
    assert out.fuse_steps == 0      # auto depth survives
    assert pre is fake              # drive never recompiles the probe's work
    assert calls == [(32, 500, True)]
    assert rep.probed and not rep.timed_out and rep.orphan is None


def test_guarded_solve_end_to_end_on_timeout(mesh, monkeypatch, capsys):
    """At test scale: a guard timeout on a default config SOLVES through
    the XLA local kernel fallback, and matches the unguarded XLA solve
    bitwise."""
    import numpy as np

    cfg = HeatConfig(n=64, ntime=20, heartbeat_every=8, dtype="float32",
                     backend="sharded", mesh_shape=(1, 1))
    ref = sharded.solve(cfg.with_(local_kernel="xla"), fetch=True)
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "0.05")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    monkeypatch.setattr(sharded, "_SAFE_FUSE", 1)  # open the depth gate
    monkeypatch.setattr(sharded, "_compile_probe",
                        lambda *a, **kw: time.sleep(30))
    got = sharded.solve(cfg, fetch=True)
    assert got.guard.timed_out
    assert got.guard.degraded["local_kernel"] == "xla"
    assert "falling back" in capsys.readouterr().out
    np.testing.assert_array_equal(np.asarray(ref.T), np.asarray(got.T))


def test_default_budget_clears_measured_flagship_compiles():
    """The budget must sit ABOVE the slowest measured cold compile of a
    program still built, or a default-config run falls into the
    fallback: flagship-scale Pallas at depth 8 cold-compiles in 6-16 min
    (benchmarks/compile_bisect_topology.json). The assert keeps the
    floor the budget was last raised to (1833 s)."""
    assert float(sharded._DEFAULT_BUDGET_S) > 1833


@pytest.mark.parametrize("why,cfg_kw,env", [
    ("explicit fuse_steps is the user's own program",
     {"fuse_steps": 32}, {}),
    ("remaining 0 compiles nothing", {"ntime": 0}, {}),
    ("xla local kernel compiles in seconds — nothing to guard",
     {"local_kernel": "xla"}, {}),
    ("f64 runs the XLA path", {"dtype": "float64"}, {}),
])
def test_guard_stays_out_of_the_way(mesh, monkeypatch, why, cfg_kw, env):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    monkeypatch.setattr(
        sharded, "_compile_probe",
        lambda *a, **kw: pytest.fail(f"probe must not run: {why}"))
    cfg = _flagship_cfg(**cfg_kw)
    out, pre, rep = sharded._guard_fuse_compile(cfg, mesh, cfg.ntime)
    assert (out, pre) == (cfg, None) and not rep.probed


def test_guard_budget_zero_skips_probe_but_joins_agreement(mesh, monkeypatch):
    """HEAT_COMPILE_BUDGET_S=0 is per-host state: it must disable the
    probe but NOT the job-wide agreement — a process skipping a collective
    its peers entered hangs the job (divergence-safety contract)."""
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "0")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    monkeypatch.setattr(
        sharded, "_compile_probe",
        lambda *a, **kw: pytest.fail("budget 0 must skip the probe"))
    joined = []

    def agree(t):
        joined.append(t)
        return t

    monkeypatch.setattr(sharded, "_agree_any_timeout", agree)
    cfg = _flagship_cfg()
    out, pre, _ = sharded._guard_fuse_compile(cfg, mesh, cfg.ntime)
    assert (out, pre) == (cfg, None)
    assert joined == [False]  # participated, voted "no timeout"


def test_guard_probe_exception_falls_back_and_joins_agreement(
        mesh, monkeypatch, capsys):
    """A probe crash (e.g. RESOURCE_EXHAUSTED on the deep unroll) must
    fall back — and still reach the agreement collective."""
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "5")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)

    def boom(*a, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: vmem")

    monkeypatch.setattr(sharded, "_compile_probe", boom)
    joined = []

    def agree(t):
        joined.append(t)
        return t

    monkeypatch.setattr(sharded, "_agree_any_timeout", agree)
    out, pre, _ = sharded._guard_fuse_compile(_flagship_cfg(), mesh, 500)
    assert out.local_kernel == "xla" and pre is None
    assert joined == [True]
    assert "probe failed" in capsys.readouterr().out


def test_guard_noop_on_cpu(mesh, monkeypatch):
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "5")
    monkeypatch.setattr(
        sharded, "_compile_probe",
        lambda *a, **kw: pytest.fail("probe must not run on cpu"))
    cfg = _flagship_cfg()
    out, pre, rep = sharded._guard_fuse_compile(cfg, mesh, cfg.ntime)
    assert (out, pre) == (cfg, None) and not rep.probed


def test_guard_noop_at_safe_depths(mesh, monkeypatch):
    # depths <= _SAFE_FUSE never probe (round 5: the chunk cap bounds
    # every such program's live compile; the probe would cost more than
    # the compile — see test_guard_skips_capped_flagship_depths)
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    monkeypatch.setattr(
        sharded, "_compile_probe",
        lambda *a, **kw: pytest.fail("k<=16 needs no guard"))
    shallow = HeatConfig(n=128, ntime=100, dtype="float32",
                         backend="sharded", mesh_shape=(1, 1))  # k* = 8
    assert sharded.fuse_depth_sharded(shallow, (1, 1)) < sharded._SAFE_FUSE
    out, pre, rep = sharded._guard_fuse_compile(shallow, mesh, 100)
    assert (out, pre) == (shallow, None) and not rep.probed

    narrow16 = HeatConfig(n=512, ntime=100, dtype="float32",
                          backend="sharded", mesh_shape=(1, 1))
    # auto k* = sqrt(512/2) = 16 — ON the boundary: no probe
    assert sharded.fuse_depth_sharded(narrow16, (1, 1)) == sharded._SAFE_FUSE
    out, pre, rep = sharded._guard_fuse_compile(narrow16, mesh, 100)
    assert (out, pre) == (narrow16, None) and not rep.probed


def test_guard_skips_capped_flagship_depths(monkeypatch):
    """Round-5 policy: the chunk cap removes the wedge family from the
    auto path (wide shards cap at k=16, live cold compile a bounded
    471 s), which is not the stall the guard exists for — so depths
    <= 16 must NOT probe. (Stub mesh: the guard reads only
    mesh.devices.shape and the probe is patched.)"""
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "0.05")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    monkeypatch.setattr(
        sharded, "_compile_probe",
        lambda *a, **kw: pytest.fail("capped depths must not probe"))

    class _Devices:
        shape = (1, 1)

    class _StubMesh:
        devices = _Devices()

    # the 16384^2 flagship: auto depth capped at 16 -> unguarded
    flagship = HeatConfig(n=16384, ntime=100, dtype="float32",
                          backend="sharded", mesh_shape=(1, 1))
    assert sharded.fuse_depth_sharded(flagship, (1, 1)) == 16
    out, pre, rep = sharded._guard_fuse_compile(flagship, _StubMesh(), 100)
    assert (out, pre) == (flagship, None) and not rep.probed

    # anisotropic wide-shallow (128-row shards of 16384^2, kf=8): also
    # unguarded — its k=8 live compile is the bounded 393 s family, not
    # the wedge
    class _Devices128:
        shape = (128, 1)

    class _StubMesh128:
        devices = _Devices128()

    aniso = HeatConfig(n=16384, ntime=100, dtype="float32",
                       backend="sharded", mesh_shape=(128, 1))
    assert sharded.fuse_depth_sharded(aniso, (128, 1)) < 16
    out, pre, rep = sharded._guard_fuse_compile(aniso, _StubMesh128(), 100)
    assert (out, pre) == (aniso, None) and not rep.probed


@pytest.mark.parametrize("padded", [True, False])
def test_compile_probe_compiles_every_chunk_size(mesh, padded):
    """The probe must cover the remainder chunk too (it unrolls the same
    deep-fused kernel and is a distinct XLA program), on the path's real
    global state shape. Runs end to end on CPU (interpret-mode pallas)."""
    cfg = HeatConfig(n=64, ntime=20, heartbeat_every=8, dtype="float32",
                     backend="sharded", mesh_shape=(1, 1), fuse_steps=4)
    pre = sharded._compile_probe(cfg, mesh, kf=4, remaining=20,
                                 padded=padded)
    assert sorted(pre) == [4, 8]  # steady 8 + remainder 20 % 8


def test_guarded_solve_uses_probe_executables(mesh, monkeypatch):
    """End-to-end on CPU: force the guard on, let the real probe compile,
    and check the solve still matches the unguarded result bitwise."""
    import numpy as np

    cfg = HeatConfig(n=64, ntime=20, heartbeat_every=8, dtype="float32",
                     backend="sharded", mesh_shape=(1, 1))
    ref = sharded.solve(cfg, fetch=True)

    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "60")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    # force the depth gate open: pretend the auto depth is past safe
    monkeypatch.setattr(sharded, "_SAFE_FUSE", 1)
    got = sharded.solve(cfg, fetch=True)
    np.testing.assert_array_equal(np.asarray(ref.T), np.asarray(got.T))


def test_solve_attaches_guard_report(mesh, monkeypatch):
    """SolveResult.guard must carry the probe's cost and verdict — a
    bench consumer has to be able to SEE that its row ran the degraded
    program (VERDICT r4 #8)."""
    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "0.05")
    monkeypatch.setattr(sharded, "_guard_platform_ok", lambda: True)
    monkeypatch.setattr(sharded, "_SAFE_FUSE", 1)
    monkeypatch.setattr(sharded, "_compile_probe",
                        lambda *a, **kw: time.sleep(30))
    cfg = HeatConfig(n=64, ntime=20, dtype="float32", backend="sharded",
                     mesh_shape=(1, 1))
    res = sharded.solve(cfg, fetch=False)
    assert res.guard is not None and res.guard.timed_out
    assert res.guard.orphan == "left_running"
    assert res.guard.degraded == {
        "local_kernel": "xla",
        "fuse_steps": sharded.fuse_depth_sharded(cfg, (1, 1))}
    assert res.timing.compile_s >= res.guard.probe_s > 0  # cost visible

    # ... and stays None when the guard never probed
    res2 = sharded.solve(cfg.with_(local_kernel="xla"), fetch=False)
    assert res2.guard is None


def test_tpu_parent_probes_in_process(mesh, monkeypatch):
    """The real platform gate: on a TPU parent the guard opens and the
    probe compiles in-process (the parent holds libtpu's lock, so no
    child could load it) and hands its executables to drive."""
    import jax

    monkeypatch.setenv("HEAT_COMPILE_BUDGET_S", "30")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    threads = []

    def probe(*a, **kw):
        import threading

        threads.append(threading.current_thread().name)
        return fake

    fake = {500: object()}
    monkeypatch.setattr(sharded, "_compile_probe", probe)
    out, pre, rep = sharded._guard_fuse_compile(_flagship_cfg(), mesh, 500)
    assert threads == ["heat-compile-guard"]
    assert rep.probed and pre is fake and not rep.timed_out
    assert out.local_kernel == "auto"  # un-degraded
