"""The program's own spans and counters (runtime/trace.py ``SPANS`` and
``COUNTERS``), the named Pallas kernels, and the benchmark readers that
reduce them (perf/layers).

- A ``Tracer.span`` lands on the profiler's host plane (the device planes'
  clock) and on the ring, once each; every call site names a table entry.
- ``drive`` counts useful against computed point-steps from the kernels'
  own plan; the lane scheduler counts its lanes' work and occupancy.
- Every ``tpu_custom_call`` of the solo and lane programs carries a
  ``heat_`` kernel name, which the device trace reports.
- The readers find what they read on hand-made events, and None without.
"""

import ast
import pathlib
import re
import types
import weakref

import numpy as np
import pytest

from heat_tpu.config import HeatConfig
from heat_tpu.runtime import trace as trace_mod

REPO = pathlib.Path(__file__).resolve().parent.parent
DRIVE_SPANS = {"heat.drive.solve", "heat.drive.compile",
               "heat.drive.dispatch", "heat.drive.sync", "heat.drive.fetch"}


@pytest.fixture
def fresh_global_tracer():
    tracer = trace_mod.configure()
    yield tracer
    trace_mod.configure()


def _traced_solve(cfg, tmp_path):
    from heat_tpu.backends import solve
    from perf import trace as perf_trace

    return perf_trace.capture(lambda: solve(cfg), str(tmp_path / "prof"))


# --- both sinks -----------------------------------------------------------------


def test_drive_spans_land_on_the_host_plane_and_the_ring_once(
        tmp_path, fresh_global_tracer):
    cfg = HeatConfig(n=32, ntime=24, dtype="float32", backend="xla")
    res, events, _ = _traced_solve(cfg, tmp_path)
    assert res.T is not None
    by = {}
    for line, name, s, e in events["host"]:
        if name.startswith("heat."):
            by.setdefault(name, []).append((line, s, e))
    assert set(by) == DRIVE_SPANS
    assert all(len(v) == 1 for v in by.values())     # one chunk, one fetch
    ((line, s0, e0),) = by["heat.drive.solve"]
    for name in ("heat.drive.dispatch", "heat.drive.sync",
                 "heat.drive.fetch"):
        ((ln, s, e),) = by[name]
        assert ln == line and s0 <= s <= e <= e0, name
    # dispatch, then the fence, then the fetch
    assert (by["heat.drive.dispatch"][0][1] < by["heat.drive.sync"][0][1]
            < by["heat.drive.fetch"][0][1])
    ring = [ev[3] for ev in fresh_global_tracer.snapshot() if ev[2] == "X"]
    assert sorted(ring) == sorted(DRIVE_SPANS)         # each recorded once
    text = "\n".join(trace_mod.summarize(fresh_global_tracer.to_chrome()))
    assert "solve fetch wall" in text


def test_a_disabled_ring_still_counts_and_records_nothing():
    off = trace_mod.Tracer(capacity=0)
    with off.span("heat.drive.sync", off.track("p", "t")) as sp:
        sp.args = {"k": 1}
    off.count("heat.lane.slots", 4)
    off.count("heat.lane.slots", 4)
    assert len(off) == 0
    assert off.counts()["heat.lane.slots"] == 8
    assert set(off.counts()) == set(trace_mod.COUNTERS)

    on = trace_mod.Tracer(capacity=8)
    with on.span("heat.drive.sync", None):
        pass                                   # no track: profiler only
    with on.span("heat.drive.fetch", on.track("p", "t"), cat="solve",
                 trace_id="x-1") as sp:
        sp.args = {"bytes": 4}
    (ev,) = on.snapshot()
    assert ev[2:5] == ("X", "heat.drive.fetch", "solve")
    assert ev[1] >= 0 and ev[7] == "x-1" and ev[8] == {"bytes": 4}


def test_counts_total_sums_the_live_tracers(monkeypatch):
    monkeypatch.setattr(trace_mod, "_TRACERS", weakref.WeakSet())
    a, b = trace_mod.Tracer(), trace_mod.Tracer(capacity=0)
    a.count("heat.drive.points_useful", 3)
    b.count("heat.drive.points_useful", 4)
    b.count("heat.lane.live", 1)
    total = trace_mod.counts_total()
    assert total["heat.drive.points_useful"] == 7
    assert total["heat.lane.live"] == 1 and total["heat.lane.slots"] == 0


def _call_names(attr):
    """First-argument string constants of every ``<x>.<attr>("heat...")``
    call in the package."""
    names = set()
    for path in (REPO / "heat_tpu").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == attr and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and str(node.args[0].value).startswith("heat.")):
                names.add(node.args[0].value)
    return names


def test_every_span_and_counter_call_site_names_the_table():
    assert _call_names("span") == set(trace_mod.SPANS)
    assert _call_names("count") == set(trace_mod.COUNTERS)
    # no span takes its name from a variable
    for path in (REPO / "heat_tpu").rglob("*.py"):
        for m in re.finditer(r"\.span\(\s*([^,)\s]+)", path.read_text()):
            assert m.group(1).startswith('"heat.'), (path, m.group(0))


# --- counters ------------------------------------------------------------------


def test_drive_counts_useful_and_computed_point_steps(tmp_path,
                                                      fresh_global_tracer):
    from heat_tpu.backends.pallas import kernel_points

    cfg = HeatConfig(n=48, ntime=40, dtype="float32", backend="pallas",
                     heartbeat_every=16)
    _traced_solve(cfg, tmp_path)
    c = fresh_global_tracer.counts()
    # chunks of 16, 16 and 8 steps (heartbeat interval, then remainder)
    assert c["heat.drive.points_useful"] == 48 * 48 * 40
    assert c["heat.kernel.points_computed"] == (
        2 * kernel_points(cfg, 16) + kernel_points(cfg, 8))
    assert c["heat.drive.points_useful"] < c["heat.kernel.points_computed"]
    ring = [ev for ev in fresh_global_tracer.snapshot()
            if ev[3] == "heat.drive.dispatch"]
    assert [ev[8]["k"] for ev in ring] == [16, 16, 8]


def test_kernel_points_of_the_4096_batch_are_the_thin_band_plan():
    """4096^2 float32 at the auto depth 16: the thin-band kernel's tile is
    256 rows with a 16-row halo each side, so 256 of every 288 computed
    rows are kept."""
    from heat_tpu.backends.pallas import kernel_points

    cfg = HeatConfig(n=4096, ntime=10000, dtype="float32", backend="pallas")
    per_pass = 16 * 288 * 4096 * 16          # 16 tiles x 288 rows x width
    assert kernel_points(cfg, 10000) == 625 * per_pass
    assert cfg.points * 10000 / kernel_points(cfg, 10000) == 256 / 288
    # float64 steps with XLA: no kernel count
    assert kernel_points(cfg.with_(dtype="float64"), 16) is None


@pytest.mark.parametrize("shape,bc", [((40, 40), "edges"),
                                      ((40, 40), "periodic"),
                                      ((12, 12, 12), "ghost")])
def test_multistep_points_cover_the_useful_work(shape, bc):
    from heat_tpu.ops.pallas_stencil import multistep_points_bc

    for k in (1, 5, 16):
        got = multistep_points_bc(shape, "float32", bc, k)
        assert got >= int(np.prod(shape)) * k


def test_lane_counters_on_a_mixed_size_wave():
    """Useful point-steps are exactly each request's n^2 x ntime; computed
    ones are lanes x slab x steps of every dispatched chunk."""
    from heat_tpu.serve import Engine, ServeConfig

    wave = [HeatConfig(n=16, ntime=24, dtype="float32"),
            HeatConfig(n=24, ntime=40, dtype="float32"),
            HeatConfig(n=10, ntime=8, dtype="float32"),
            HeatConfig(n=20, ntime=33, dtype="float32")]
    eng = Engine(ServeConfig(emit_records=False, lanes=2, chunk=8,
                             buckets=(24,), lane_kernel="xla"))
    for cfg in wave:
        eng.submit(cfg)
    recs = eng.results()
    assert all(r["status"] == "ok" for r in recs)
    c = eng.tracer.counts()
    ks = [ev[8]["k"] for ev in eng.tracer.snapshot()
          if ev[3] == "heat.sched.dispatch"]
    assert len(ks) == eng.chunks_dispatched
    slab = (24 + 2) ** 2
    assert c["heat.lane.points_useful"] == sum(cfg.points * cfg.ntime
                                               for cfg in wave)
    assert c["heat.lane.points_computed"] == 2 * slab * sum(ks)
    assert c["heat.lane.slots"] == 2 * len(ks)
    assert 0 < c["heat.lane.live"] <= c["heat.lane.slots"]
    assert c["heat.lane.points_useful"] <= c["heat.lane.points_computed"]
    names = {ev[3] for ev in eng.tracer.snapshot() if ev[2] == "X"}
    assert {"heat.lane.load", "heat.sched.boundary_fetch",
            "heat.lane.writeback"} <= names


# --- named kernels ---------------------------------------------------------------

KERNELS = {"heat_ftcs_2d", "heat_ftcs_2d_coltiled", "heat_ftcs_3d",
           "heat_lane_2d", "heat_lane_3d"}


def _tpu_kernel_names(fn, *args):
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call(" in ln]
    assert calls
    return [m.group(1) if (m := re.search(r'kernel_name = "([^"]*)"', ln))
            else None for ln in calls]


def test_every_tpu_custom_call_carries_a_heat_kernel_name():
    import jax
    import jax.numpy as jnp

    from heat_tpu.backends.pallas import make_advance
    from heat_tpu.ops import pallas_stencil as ps
    from heat_tpu.serve.engine import (BucketKey, _lane_structs,
                                       make_lane_advance)

    seen = []
    # Mosaic lowers an f32/i32 world; the suite enables x64 (f64 oracle)
    jax.config.update("jax_enable_x64", False)
    try:
        with ps.force_compiled_kernels():
            for cfg in (HeatConfig(n=64, ntime=8, dtype="float32",
                                   bc="edges", backend="pallas"),
                        HeatConfig(n=64, ntime=8, dtype="float32",
                                   bc="periodic", backend="pallas"),
                        HeatConfig(n=12, ndim=3, ntime=8, dtype="float32",
                                   bc="ghost", backend="pallas")):
                T = jax.ShapeDtypeStruct(cfg.shape, jnp.float32)
                seen += _tpu_kernel_names(make_advance(cfg), T, 8)
            col = jax.jit(lambda T: ps._pallas_2d_coltiled(
                T, r=0.1, ksteps=4, R=16, C=256, kr=8, kc=128,
                logical_shape=(32, 512)))
            seen += _tpu_kernel_names(
                col, jax.ShapeDtypeStruct((32, 512), jnp.float32))
            for key in (BucketKey(2, 64, "float32", "edges"),
                        BucketKey(3, 16, "float32", "ghost")):
                adv = make_lane_advance(key, kernel="pallas")
                seen += _tpu_kernel_names(
                    adv, *_lane_structs(key, 2, "pallas"), 8)
    finally:
        jax.config.update("jax_enable_x64", True)
        jax.clear_caches()
    assert all(n is not None and n.startswith("heat_") for n in seen), seen
    assert set(seen) == KERNELS


# --- readers (perf/layers) on hand-made events --------------------------------------


def _reader(name):
    from perf import registry

    return registry.reader("layers", name).read


def _run(events, window_ns=1000):
    return types.SimpleNamespace(events=events, trace_window_s=window_ns / 1e9,
                                 records=[])


OPS = [("XLA Ops", "%heat_ftcs_2d.1 = f32[8,128] custom-call(), "
        'custom_call_target="tpu_custom_call"', 0.0, 100.0),
       ("XLA Ops", "%copy.11 = f32[8,128] copy()", 200.0, 300.0)]


@pytest.mark.parametrize("fetch,want", [
    ((90.0, 210.0), 10.0),     # open over the gap [100, 200]: 100 of 1000
    ((20.0, 80.0), 0.0),       # inside an op: the device is busy
    ((250.0, 600.0), 30.0),    # [300, 600] exposed
])
def test_drive_fetch_idle_share_reads_fetch_spans_over_device_gaps(fetch,
                                                                   want):
    host = [("python", "heat.drive.fetch", *fetch),
            ("python", "heat.drive.solve", 0.0, 900.0)]
    read = _reader("drive_fetch_idle_share")
    assert read(_run({0: OPS, "host": host})) == pytest.approx(want)
    # two devices: the mean of their exposed shares
    two = _run({0: OPS, 1: [("XLA Ops", "%x = f32[] add()", 0.0, 1000.0)],
                "host": host})
    assert read(two) == pytest.approx(want / 2)


def test_drive_fetch_idle_share_is_none_without_fetch_spans():
    read = _reader("drive_fetch_idle_share")
    assert read(_run({0: OPS, "host": [("python", "perf.solve", 0.0,
                                        500.0)]})) is None
    assert read(_run({})) is None


def test_stencil_busy_share_counts_named_kernels_only():
    read = _reader("stencil_busy_share")
    assert read(_run({0: OPS})) == pytest.approx(50.0)
    unnamed = [("XLA Ops", '%_pallas_2d.3 = f32[8,128] custom-call(), '
                'custom_call_target="tpu_custom_call"', 0.0, 100.0),
               OPS[1]]
    assert read(_run({0: unnamed})) is None
    lane = [("XLA Ops", "%heat_lane_2d.7 = (f32[2,8,128]) custom-call()",
             0.0, 300.0), ("XLA Ops", "%copy.2 = f32[8,128] copy()",
                           300.0, 400.0)]
    assert read(_run({0: lane})) == pytest.approx(75.0)
    assert read(_run({})) is None


@pytest.mark.parametrize("name,num,den,span", [
    ("kernel_useful_share", "heat.drive.points_useful",
     "heat.kernel.points_computed", "heat.drive.dispatch"),
    ("padding_useful_share", "heat.lane.points_useful",
     "heat.lane.points_computed", "heat.sched.dispatch"),
    ("lane_occupancy", "heat.lane.live", "heat.lane.slots",
     "heat.sched.dispatch"),
])
def test_counter_readers_read_the_ratio_of_their_run(name, num, den, span,
                                                     monkeypatch):
    from perf import counters

    read = _reader(name)
    traced = _run({"host": [("python", span, 0.0, 1.0)]})
    monkeypatch.setattr(counters, "totals", lambda: {num: 256, den: 288})
    assert read(traced) == pytest.approx(100.0 * 256 / 288)
    # no span of the counting dispatches in the run's trace
    assert read(_run({"host": [("python", "perf.solve", 0.0, 1.0)]})) is None
    assert read(_run({})) is None
    # a program that keeps no counters, or counted nothing
    monkeypatch.setattr(counters, "totals", lambda: {})
    assert read(traced) is None
    monkeypatch.setattr(counters, "totals", lambda: {num: 0, den: 0})
    assert read(traced) is None


def test_counter_totals_come_from_the_program(monkeypatch):
    from perf import counters

    monkeypatch.setattr(trace_mod, "_TRACERS", weakref.WeakSet())
    t = trace_mod.Tracer()
    t.count("heat.lane.live", 3)
    t.count("heat.lane.slots", 4)
    assert counters.totals()["heat.lane.live"] == 3
    monkeypatch.delattr(trace_mod, "counts_total")
    assert counters.totals() == {}
