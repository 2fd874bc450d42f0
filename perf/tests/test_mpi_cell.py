"""The sharded cell's own readings: ``kernel_useful_share`` from the
program's counters on the sharded path, and ``exchange_gbps`` from the
halo counters over the collectives' device time."""

import types

import pytest

from perf import counters, registry, run

SEED = 2 ** 31 + 909


def _reader(name):
    return registry.reader("layers", name).read


def test_kernel_useful_share_reads_on_the_sharded_cell(on_cpu):
    on_cpu("mpi-32768-2x2")
    r, res = run.run_cell(["--workload", "mpi-32768-2x2", "--seed",
                           str(SEED), "--seconds", "0.5", "--trace", "1"])
    assert res["correct"], res["checks"]
    share = res["metrics"]["kernel_useful_share"]["value"]
    assert 0 < share < 100
    c = counters.totals()
    assert c["heat.halo.exchanges"] > 0 and c["heat.halo.bytes"] > 0


def _run(events, n=16, ntime=8, solves=2):
    return types.SimpleNamespace(
        events=events, trace_window_s=1.0,
        records=[{"start": i, "end": i + 1, "points": n * n * ntime}
                 for i in range(solves)],
        cfg={"n": n, "ntime": ntime, "dtype": "float32"})


PERMUTE = "%collective-permute-start.2 = (f32[4,40]) collective-permute-start()"
TWO_DEVICES = {
    0: [("XLA Ops", PERMUTE, 0.0, 100.0),
        ("Async XLA Ops", "%collective-permute-done.2", 50.0, 300.0),
        ("XLA Ops", "%heat_ftcs_2d.1 = f32[40,40] custom-call()", 300.0,
         900.0)],
    1: [("XLA Ops", PERMUTE, 0.0, 500.0)],
}


def test_exchange_gbps_is_the_window_bytes_over_each_devices_permutes(
        monkeypatch):
    # three solves counted (set-up's warm one and the window's two) sent
    # 3000 bytes: 2000 in the window; device 0 permutes for 300 ns (a
    # union), device 1 for 500
    monkeypatch.setattr(counters, "totals", lambda: {
        "heat.halo.bytes": 3000, "heat.drive.points_useful": 3 * 16 * 16 * 8})
    got = _reader("exchange_gbps")(_run(TWO_DEVICES))
    assert got == pytest.approx((2000 / 300 + 2000 / 500) / 2)


def test_exchange_gbps_is_none_with_nothing_to_read(monkeypatch):
    read = _reader("exchange_gbps")
    counted = {"heat.halo.bytes": 3000,
               "heat.drive.points_useful": 3 * 16 * 16 * 8}
    monkeypatch.setattr(counters, "totals", lambda: counted)
    assert read(_run({})) is None                       # no events
    no_permute = {0: [TWO_DEVICES[0][2]]}
    assert read(_run(no_permute)) is None               # no collectives
    assert read(_run(TWO_DEVICES, solves=0)) is None    # no solves
    # a program without the halo counters, or one that counted nothing
    for totals in ({}, {"heat.drive.points_useful": 100},
                   {"heat.halo.bytes": 0, "heat.drive.points_useful": 100}):
        monkeypatch.setattr(counters, "totals", lambda t=totals: t)
        assert read(_run(TWO_DEVICES)) is None
