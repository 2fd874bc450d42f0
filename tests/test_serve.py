"""Serving engine: batched-lane correctness, continuous batching, bucket
admission, per-request fault isolation, and the serve CLI.

The load-bearing contract is bit-identity: a request served through the
vmapped masked lanes must produce, on CPU at the same dtype, the exact
bytes a solo ``heat-tpu run`` of the same config produces — including
requests admitted mid-flight into a lane another request just vacated
(the continuous-batching path)."""

import json

import numpy as np
import pytest

from heat_tpu.backends import solve
from heat_tpu.config import HeatConfig, config_from_request
from heat_tpu.runtime import faults
from heat_tpu.serve import Engine, ServeConfig
from heat_tpu.serve import engine as engine_mod
from heat_tpu.serve.engine import (BucketKey, LaneEngine, lane_buffer,
                                   lane_tier, tail_size)


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


def quiet(**kw) -> ServeConfig:
    kw.setdefault("emit_records", False)
    return ServeConfig(**kw)


# --- batched-engine bit-identity -------------------------------------------


MIXED_REQUESTS = [
    # mixed sizes, BCs, diffusivities, step counts — all one engine run;
    # 6 requests over 2 lanes forces continuous-batching admission
    HeatConfig(n=17, ntime=37, dtype="float64", bc="edges", ic="hat"),
    HeatConfig(n=32, ntime=50, dtype="float64", bc="ghost", ic="uniform"),
    HeatConfig(n=24, ntime=5, dtype="float64", bc="edges", ic="hat_small",
               nu=0.1),
    HeatConfig(n=40, ntime=20, dtype="float64", bc="edges", ic="hat"),
    HeatConfig(n=20, ntime=64, dtype="float64", bc="ghost", ic="hat",
               bc_value=2.5),
    HeatConfig(n=17, ntime=3, dtype="float64", bc="ghost", ic="hat_half"),
]


def test_batched_lanes_bit_identical_to_solo_runs():
    """Acceptance: every lane's final field == the solo run of the same
    config, bitwise, including lanes filled mid-flight (6 requests, 2
    lanes -> 4 of them are continuous-batching admits)."""
    eng = Engine(quiet(lanes=2, chunk=8, buckets=(32, 48)))
    ids = [eng.submit(cfg) for cfg in MIXED_REQUESTS]
    recs = {r["id"]: r for r in eng.results()}
    for cfg, rid in zip(MIXED_REQUESTS, ids):
        rec = recs[rid]
        assert rec["status"] == "ok", rec
        solo = solve(cfg).T
        assert solo.dtype == rec["T"].dtype
        np.testing.assert_array_equal(rec["T"], solo)


def test_mid_flight_admit_into_freed_lane_is_exact():
    """One lane, three requests: requests 2 and 3 can ONLY run by being
    swapped into the lane request 1 finished in. Different step counts so
    the masked per-step countdown (not the chunk size) sets each stop."""
    cfgs = [HeatConfig(n=16, ntime=k, dtype="float64", bc="edges")
            for k in (7, 19, 30)]  # none a multiple of chunk=8
    eng = Engine(quiet(lanes=1, chunk=8, buckets=(16,)))
    ids = [eng.submit(c) for c in cfgs]
    recs = {r["id"]: r for r in eng.results()}
    for cfg, rid in zip(cfgs, ids):
        np.testing.assert_array_equal(recs[rid]["T"], solve(cfg).T)
    # one lane means one (bucket, lane-count) combo: exactly one compile
    assert eng.step_compiles == 1


def test_float32_and_bfloat16_lanes_match_solo():
    for dtype in ("float32", "bfloat16"):
        cfgs = [HeatConfig(n=12, ntime=9, dtype=dtype, bc="edges"),
                HeatConfig(n=16, ntime=14, dtype=dtype, bc="ghost",
                           ic="uniform")]
        eng = Engine(quiet(lanes=2, chunk=4, buckets=(16,)))
        ids = [eng.submit(c) for c in cfgs]
        recs = {r["id"]: r for r in eng.results()}
        for cfg, rid in zip(cfgs, ids):
            solo = solve(cfg).T
            assert np.array_equal(np.asarray(recs[rid]["T"], np.float32),
                                  np.asarray(solo, np.float32))


def test_3d_requests_served():
    cfg = HeatConfig(n=10, ntime=6, ndim=3, dtype="float64", bc="ghost",
                     ic="uniform", sigma=0.1)
    eng = Engine(quiet(lanes=2, chunk=4, buckets=(12,)))
    rid = eng.submit(cfg)
    rec = eng.results()[0]
    assert rec["status"] == "ok" and rec["id"] == rid
    np.testing.assert_array_equal(rec["T"], solve(cfg).T)


def test_zero_step_request_returns_ic():
    cfg = HeatConfig(n=8, ntime=0, dtype="float64")
    eng = Engine(quiet(lanes=1, chunk=4, buckets=(8,)))
    eng.submit(cfg)
    np.testing.assert_array_equal(eng.results()[0]["T"], solve(cfg).T)


def test_compile_count_one_per_bucket_lane_combo():
    """Acceptance: at most one stepping compile per (bucket, lane-count),
    however many requests flow through — and a second wave of submits
    reuses the warm programs (zero new compiles)."""
    cfgs = [HeatConfig(n=n, ntime=4, dtype="float64")
            for n in (8, 10, 12, 14, 16, 9, 11, 13)]
    eng = Engine(quiet(lanes=3, chunk=4, buckets=(12, 16)))
    for c in cfgs:
        eng.submit(c)
    eng.results()
    # two buckets, both with >= 3 requests -> exactly two combos
    assert eng.step_compiles == 2
    before = eng.step_compiles
    for c in cfgs:
        eng.submit(c)
    recs = eng.results()
    assert eng.step_compiles == before  # warm reuse across waves
    assert sum(r["status"] == "ok" for r in recs) == 2 * len(cfgs)


# --- dispatch-ahead (ISSUE 4) ----------------------------------------------


@pytest.mark.parametrize("depth", [0, 1, 2, 4])
def test_bit_identity_at_every_dispatch_depth(depth):
    """Acceptance: served results are bit-identical to solo runs at every
    dispatch depth — including depth=0 (the sync fallback) and depths
    deeper than the chunk count — with mid-flight admits (6+1 requests
    over 2 lanes) and a zero-step request in the mix."""
    cfgs = MIXED_REQUESTS + [HeatConfig(n=12, ntime=0, dtype="float64")]
    eng = Engine(quiet(lanes=2, chunk=8, buckets=(32, 48),
                       dispatch_depth=depth))
    ids = [eng.submit(cfg) for cfg in cfgs]
    recs = {r["id"]: r for r in eng.results()}
    for cfg, rid in zip(cfgs, ids):
        assert recs[rid]["status"] == "ok", recs[rid]
        np.testing.assert_array_equal(recs[rid]["T"], solve(cfg).T)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_low_precision_lanes_identical_across_depths(dtype):
    """f32/bf16 results must not depend on the dispatch depth: the depth
    changes WHEN boundaries are inspected, never what the device steps."""
    cfgs = [HeatConfig(n=12, ntime=9, dtype=dtype, bc="edges"),
            HeatConfig(n=16, ntime=14, dtype=dtype, bc="ghost",
                       ic="uniform"),
            HeatConfig(n=10, ntime=21, dtype=dtype, bc="edges", nu=0.1)]
    fields = {}
    for depth in (0, 2, 3):
        eng = Engine(quiet(lanes=2, chunk=4, buckets=(16,),
                           dispatch_depth=depth))
        ids = [eng.submit(c) for c in cfgs]
        recs = {r["id"]: r for r in eng.results()}
        fields[depth] = [np.asarray(recs[rid]["T"], np.float32)
                         for rid in ids]
    for cfg, a, b, c in zip(cfgs, fields[0], fields[2], fields[3]):
        solo = np.asarray(solve(cfg).T, np.float32)
        np.testing.assert_array_equal(a, solo)
        np.testing.assert_array_equal(b, solo)
        np.testing.assert_array_equal(c, solo)


def test_dispatch_does_not_fence():
    """Regression (ISSUE 4): the hot loop must dispatch ahead — with
    depth 2, TWO chunk programs are queued before the first boundary
    fetch happens, and dispatching itself never touches host memory.
    host_fetch is the one D2H seam, so event order proves the shape."""
    events = []
    real_fetch = engine_mod.host_fetch
    real_dispatch = LaneEngine.dispatch_chunk

    def spy_fetch(x):
        events.append("fetch")
        return real_fetch(x)

    def spy_dispatch(self, k=None):
        events.append("dispatch")
        return real_dispatch(self, k)

    cfg = HeatConfig(n=16, ntime=32, dtype="float64")  # 4 chunks of 8
    eng = Engine(quiet(lanes=1, chunk=8, buckets=(16,), dispatch_depth=2))
    eng.submit(cfg)
    try:
        engine_mod.host_fetch = spy_fetch
        LaneEngine.dispatch_chunk = spy_dispatch
        recs = eng.results()
    finally:
        engine_mod.host_fetch = real_fetch
        LaneEngine.dispatch_chunk = real_dispatch
    assert recs[0]["status"] == "ok"
    # the pipeline primes to depth 2 before the first boundary D2H
    assert events[:3] == ["dispatch", "dispatch", "fetch"], events
    assert events.count("dispatch") == 4
    # every boundary was inspected exactly once, none re-fetched
    assert eng.boundary_waits == eng.chunks_dispatched == 4


def test_lane_tiers_share_programs_across_uneven_waves():
    """The lane-tier rule: waves of 3 then 5 requests under a --lanes 4
    cap round up to the SAME tier (4) and reuse one compiled stepping
    program; under a cap of 8 they land on tiers 4 and 8 (two programs,
    not three when a wave of 6 follows)."""
    assert [lane_tier(n, 4) for n in (1, 2, 3, 4, 5, 8)] == [1, 2, 4, 4, 4, 4]
    assert [lane_tier(n, 8) for n in (3, 5, 6, 9)] == [4, 8, 8, 8]

    def wave(eng, count):
        ids = [eng.submit(HeatConfig(n=12, ntime=6, dtype="float64"))
               for _ in range(count)]
        recs = {r["id"]: r for r in eng.results()}
        assert all(recs[i]["status"] == "ok" for i in ids)

    eng = Engine(quiet(lanes=4, chunk=4, buckets=(16,)))
    wave(eng, 3)
    assert eng.step_compiles == 1          # tier 4
    wave(eng, 5)
    assert eng.step_compiles == 1          # tier 4 again: warm reuse
    eng8 = Engine(quiet(lanes=8, chunk=4, buckets=(16,)))
    wave(eng8, 3)
    wave(eng8, 5)
    wave(eng8, 6)
    assert eng8.step_compiles == 2         # tiers 4 and 8, wave 3 reuses


def test_tail_chunks_bounded_compiles_and_exact():
    """Tail-waste fix: when every live lane's countdown drops below the
    chunk, the group switches to the quarter-chunk tail program — at most
    ONE tail compile per (bucket, lane-tier) across waves, and the
    results stay bit-identical."""
    assert tail_size(16) == 4 and tail_size(4) == 1
    assert tail_size(2) == 1 and tail_size(1) is None
    cfgs = [HeatConfig(n=12, ntime=21, dtype="float64"),
            HeatConfig(n=12, ntime=10, dtype="float64", nu=0.1)]
    eng = Engine(quiet(lanes=2, chunk=16, buckets=(16,)))
    ids = [eng.submit(c) for c in cfgs]
    recs = {r["id"]: r for r in eng.results()}
    for cfg, rid in zip(cfgs, ids):
        np.testing.assert_array_equal(recs[rid]["T"], solve(cfg).T)
    assert eng.tail_chunks >= 1
    assert eng.tail_compiles == 1
    # a second same-tier wave hitting the tail regime reuses the compiled
    # tail (two requests again — one request would be tier 1, a new combo)
    wave2 = [HeatConfig(n=12, ntime=5, dtype="float64"),
             HeatConfig(n=12, ntime=7, dtype="float64")]
    ids = [eng.submit(c) for c in wave2]
    recs = {r["id"]: r for r in eng.results()}
    for cfg, rid in zip(wave2, ids):
        np.testing.assert_array_equal(recs[rid]["T"], solve(cfg).T)
    assert eng.tail_compiles == 1 and eng.step_compiles == 1


def test_sink_error_isolated_under_async_extraction(tmp_path):
    """Fault isolation survives the async-extraction rework at depth > 1:
    the failing request's D2H + write run in the writer thread, fail that
    record, and the pipelined lanes keep draining bit-exact."""
    out = tmp_path / "results"
    eng = Engine(quiet(lanes=2, chunk=4, buckets=(32,), out_dir=str(out),
                       keep_fields=True, dispatch_depth=3))
    good1 = eng.submit(HeatConfig(n=16, ntime=10, dtype="float64"))
    bad = eng.submit(HeatConfig(n=16, ntime=10, dtype="float64",
                                inject="sink-error@0:times=99"))
    good2 = eng.submit(HeatConfig(n=16, ntime=10, dtype="float64", nu=0.1))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[bad]["status"] == "error"
    assert "injected transient sink error" in recs[bad]["error"]
    assert not (out / f"{bad}.npz").exists()
    for rid in (good1, good2):
        assert recs[rid]["status"] == "ok"
        with np.load(out / f"{rid}.npz") as z:
            np.testing.assert_array_equal(z["T"], recs[rid]["T"])
    solo = solve(HeatConfig(n=16, ntime=10, dtype="float64")).T
    np.testing.assert_array_equal(recs[good1]["T"], solo)


def test_summary_surfaces_dispatch_counters():
    eng = Engine(quiet(lanes=2, chunk=8, buckets=(16,)))
    for _ in range(3):
        eng.submit(HeatConfig(n=12, ntime=20, dtype="float64"))
    eng.results()
    s = eng.summary()
    assert s["dispatch_depth"] == 2
    assert s["chunks_dispatched"] >= 3
    assert s["boundary_waits"] >= 1 and s["boundary_wait_s"] >= 0
    assert set(s) >= {"tail_chunks", "tail_compiles", "device_idle_s"}
    # and the run left a Timing carrying the serve fields
    assert eng.timing is not None and eng.timing.dispatch_depth == 2
    assert any("serve dispatch" in l for l in eng.timing.report_lines())


# --- admission / rejection --------------------------------------------------


def test_bucket_overflow_is_a_per_request_rejection():
    # mega_lanes=0 pins the single-tier shape (ISSUE 10: on a
    # multi-device host, overflow otherwise runs as a sharded mega-lane
    # — tests/test_serve_mega.py owns that path)
    eng = Engine(quiet(buckets=(32, 64), mega_lanes=0))
    big = eng.submit(HeatConfig(n=100, ntime=5))
    ok = eng.submit(HeatConfig(n=16, ntime=5, dtype="float64"))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[big]["status"] == "rejected"
    assert "bucket-overflow" in recs[big]["error"]
    assert recs[big]["hint"] == "enable --mega-lanes"
    assert recs[ok]["status"] == "ok"  # the engine kept serving


def test_periodic_bc_rejected_not_mis_served():
    eng = Engine(quiet(buckets=(32,)))
    rid = eng.submit(HeatConfig(n=16, ntime=5, bc="periodic"))
    rec = eng.results()[0]
    assert rec["status"] == "rejected" and "periodic" in rec["error"]
    assert rid == rec["id"]


def test_bucket_selection_smallest_fit():
    eng = Engine(quiet(lanes=1, buckets=(64, 16, 32)))
    rids = [eng.submit(HeatConfig(n=n, ntime=1, dtype="float64"))
            for n in (16, 17, 33)]
    recs = {r["id"]: r for r in eng.results()}
    assert [recs[r]["bucket"] for r in rids] == [16, 32, 64]


def test_lane_engine_rejects_periodic_and_bad_geometry():
    with pytest.raises(ValueError, match="periodic"):
        LaneEngine(BucketKey(2, 16, "float64", "periodic"), 1, 4)
    key = BucketKey(2, 16, "float64", "edges")
    with pytest.raises(ValueError, match="exceeds bucket"):
        lane_buffer(key, np.ones((32, 32)), 1.0)
    with pytest.raises(ValueError, match="square"):
        lane_buffer(key, np.ones((8, 4)), 1.0)


def test_queue_wait_and_lane_metadata_recorded():
    eng = Engine(quiet(lanes=1, chunk=4, buckets=(16,)))
    for _ in range(3):
        eng.submit(HeatConfig(n=8, ntime=8, dtype="float64"))
    recs = eng.results()
    assert all(r["lane"] == 0 for r in recs)
    assert all(r["queue_wait_s"] >= 0 for r in recs)
    assert all(r["steps_per_s"] > 0 for r in recs)
    # FIFO admission: later submits waited at least as long
    waits = [r["queue_wait_s"] for r in recs]
    assert waits == sorted(waits)


# --- writeback + fault isolation -------------------------------------------


def test_sink_error_fails_one_request_engine_keeps_draining(tmp_path):
    """Acceptance: a sink-error injected on one request's writeback fails
    THAT record; the other lanes' results land intact."""
    out = tmp_path / "results"
    eng = Engine(quiet(lanes=2, chunk=4, buckets=(32,), out_dir=str(out),
                       keep_fields=True))
    good1 = eng.submit(HeatConfig(n=16, ntime=10, dtype="float64"))
    bad = eng.submit(HeatConfig(n=16, ntime=10, dtype="float64",
                                inject="sink-error@0:times=99"))
    good2 = eng.submit(HeatConfig(n=16, ntime=10, dtype="float64", nu=0.1))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[bad]["status"] == "error"
    assert "injected transient sink error" in recs[bad]["error"]
    assert not (out / f"{bad}.npz").exists()
    for rid in (good1, good2):
        assert recs[rid]["status"] == "ok"
        with np.load(out / f"{rid}.npz") as z:
            np.testing.assert_array_equal(z["T"], recs[rid]["T"])


def test_transient_sink_error_recovers_via_writer_retry(tmp_path):
    """times=1 is within SnapshotWriter's bounded retry budget: the
    request must end ok, not error."""
    out = tmp_path / "results"
    eng = Engine(quiet(lanes=1, chunk=4, buckets=(32,), out_dir=str(out)))
    rid = eng.submit(HeatConfig(n=16, ntime=10, dtype="float64",
                                inject="sink-error@0:times=1"))
    rec = eng.results()[0]
    assert rec["status"] == "ok"
    assert (out / f"{rid}.npz").exists()


def test_result_files_atomic_and_loadable(tmp_path):
    out = tmp_path / "r"
    cfg = HeatConfig(n=12, ntime=6, dtype="float64")
    eng = Engine(quiet(lanes=1, chunk=4, buckets=(16,), out_dir=str(out)))
    rid = eng.submit(cfg)
    eng.results()
    assert not list(out.glob("*.tmp"))  # atomic publish: no torn temps
    with np.load(out / f"{rid}.npz") as z:
        np.testing.assert_array_equal(z["T"], solve(cfg).T)
        assert int(z["step"]) == cfg.ntime


# --- request JSONL + CLI ----------------------------------------------------


def test_config_from_request_validates_and_coerces():
    cfg = config_from_request({"id": "x", "n": 24.0, "ntime": 7,
                               "nu": 0.1, "bc": "ghost"})
    assert cfg.n == 24 and isinstance(cfg.n, int)
    assert cfg.nu == 0.1 and cfg.bc == "ghost"
    with pytest.raises(ValueError, match="unknown request key"):
        config_from_request({"n": 8, "backend": "pallas"})
    with pytest.raises(ValueError):
        config_from_request({"n": 8, "dtype": "float16"})


def test_serve_cli_end_to_end(tmp_cwd, capsys):
    from heat_tpu.cli import main

    reqs = tmp_cwd / "reqs.jsonl"
    reqs.write_text(
        "# comment line\n"
        '{"id": "a", "n": 24, "ntime": 16, "dtype": "float64"}\n'
        "\n"
        '{"n": 40, "ntime": 8, "bc": "ghost", "ic": "uniform", '
        '"dtype": "float64"}\n')
    rc = main(["serve", "--requests", "reqs.jsonl", "--lanes", "2",
               "--chunk", "8", "--buckets", "32,48", "--out-dir", "res",
               "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    records = [json.loads(l) for l in out.splitlines()
               if l.startswith("{") and '"serve_request"' in l]
    assert {r["id"] for r in records} == {"a", "req-0001"}
    assert all(r["status"] == "ok" for r in records)
    # the library-visible result equals the solo run of the same request
    with np.load(tmp_cwd / "res" / "a.npz") as z:
        solo = solve(HeatConfig(n=24, ntime=16, dtype="float64")).T
        np.testing.assert_array_equal(z["T"], solo)
    assert "served 2 request(s): 2 ok" in out


def test_serve_cli_bad_requests_nonzero_exit(tmp_cwd, capsys):
    from heat_tpu.cli import main

    (tmp_cwd / "reqs.jsonl").write_text(
        '{"n": 9999, "ntime": 1}\n'      # bucket overflow
        'garbage\n'                       # parse failure
        '{"n": 16, "ntime": 2, "dtype": "float64"}\n')
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "64"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "1 ok" in out and "2 rejected" in out


def test_serve_cli_dispatch_depth_off_and_bad_value(tmp_cwd, capsys):
    from heat_tpu.cli import main

    reqs = tmp_cwd / "reqs.jsonl"
    reqs.write_text('{"id": "a", "n": 16, "ntime": 12, "dtype": "float64"}\n')
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "16",
               "--chunk", "4", "--dispatch-depth", "off"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "dispatch: depth 0" in out
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "16",
               "--dispatch-depth", "sideways"])
    assert rc == 2
    assert "dispatch-depth" in capsys.readouterr().err


def test_serve_lab_ab_harness_smoke(tmp_path, capsys):
    """The serve_lab A/B harness (dispatch-ahead vs sync vs sequential)
    runs end-to-end on a tiny 2-lane workload and emits every field the
    committed artifact relies on. Speed thresholds deliberately NOT
    asserted — 6 requests on a loaded CI box prove plumbing, not perf."""
    import importlib.util
    import sys
    from pathlib import Path

    bench_dir = Path(__file__).resolve().parent.parent / "benchmarks"
    sys.path.insert(0, str(bench_dir))
    try:
        spec = importlib.util.spec_from_file_location(
            "serve_lab_smoke", bench_dir / "serve_lab.py")
        serve_lab = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(serve_lab)
        out = tmp_path / "serve_lab.json"
        serve_lab.main(["--requests", "6", "--lanes", "2", "--chunk", "8",
                        "--out", str(out)])
    finally:
        sys.path.remove(str(bench_dir))
    rec = json.loads(out.read_text())
    assert rec["bench"] == "serve_lab"
    # the 2 permanently-oversized requests (ISSUE 10): served as mega
    # lanes on this 8-device harness, rejected-with-hint on one device
    over = rec["oversized"]
    assert over["count"] == 2
    if over["expected"] == "mega":
        exp_ok, exp_rej = 6 + 2, 0
        assert over["statuses"] == ["ok"] * 4
    else:
        exp_ok, exp_rej = 6, 2
        assert over["hint_present"] is True
    for side in ("engine", "engine_sync"):
        assert rec[side]["ok"] == exp_ok
        assert rec[side]["rejected"] == exp_rej
        assert rec[side]["bit_identical_sample"] is True
        assert rec[side]["boundary_wait_s"] >= 0
        assert "device_idle_frac_est" in rec[side]
    assert rec["engine"]["dispatch_depth"] == 2
    assert rec["engine_sync"]["dispatch_depth"] == 0
    assert rec["dispatch_ab_speedup"] is not None
    assert rec["one_compile_per_bucket_lane_tier"] is True


def test_serve_frontend_lab_harness_smoke(tmp_path):
    """The front-end lab harness (offline policy-layer drain + online
    Poisson EDF-vs-FIFO A/B) runs end-to-end on a tiny 8-request load
    and emits every field the committed artifact relies on. Which policy
    meets more deadlines is a wall-clock outcome on 8 requests, so it is
    not asserted: only that the lab's exit code reports the A/B it
    recorded."""
    import importlib.util
    import sys
    from pathlib import Path

    bench_dir = Path(__file__).resolve().parent.parent / "benchmarks"
    sys.path.insert(0, str(bench_dir))
    try:
        spec = importlib.util.spec_from_file_location(
            "serve_frontend_lab_smoke", bench_dir / "serve_frontend_lab.py")
        lab = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lab)
        out = tmp_path / "serve_frontend_lab.json"
        rc = lab.main(["--requests", "8", "--lanes", "2", "--chunk", "8",
                       "--out", str(out)])
    finally:
        sys.path.remove(str(bench_dir))
    rec = json.loads(out.read_text())
    assert rec["bench"] == "serve_frontend_lab"
    assert rec["offline_drain"]["ok"] == 8
    # small runs skip the committed-baseline compare (population differs)
    assert rec["offline_drain"]["vs_serve_lab_engine"] is None
    for side in ("online_fifo", "online_edf"):
        blk = rec[side]
        assert sum(blk["statuses"].values()) == 8
        assert blk["deadline_carrying"] == 4
        assert blk["deadline_hit_rate"] is not None
        assert "latency_quantiles_s" in blk
    edf_held = (rec["online_edf"]["deadline_hit_rate"]
                >= rec["online_fifo"]["deadline_hit_rate"])
    assert rc == (0 if edf_held else 1)


def test_serve_cli_missing_file(tmp_cwd, capsys):
    from heat_tpu.cli import main

    rc = main(["serve", "--requests", "nope.jsonl"])
    assert rc == 2
    assert "not found" in capsys.readouterr().err


def test_serve_cli_requires_requests_or_listen(capsys):
    from heat_tpu.cli import main

    rc = main(["serve"])
    assert rc == 2
    assert "--listen" in capsys.readouterr().err


# --- per-lane fault domains (ISSUE 5) ---------------------------------------


def _run_wave(tmp_path, tag, cfgs, **kw):
    """Drain one wave through a fresh engine writing <tag>/ npz files;
    returns (engine, {id: record})."""
    faults.reset()
    out = tmp_path / tag
    eng = Engine(quiet(lanes=2, chunk=8, buckets=(32,), out_dir=str(out),
                       keep_fields=True, **kw))
    ids = [eng.submit(cfg, request_id=f"r{i}")
           for i, cfg in enumerate(cfgs)]
    recs = {r["id"]: r for r in eng.results()}
    return eng, recs, out, ids


QUAR_WAVE = [HeatConfig(n=16, ntime=40, dtype="float64"),
             HeatConfig(n=24, ntime=56, dtype="float64", ic="hat_small"),
             HeatConfig(n=16, ntime=40, dtype="float64", nu=0.1),
             HeatConfig(n=20, ntime=24, dtype="float64", bc="ghost",
                        ic="uniform")]


@pytest.mark.parametrize("depth", [0, 2])
def test_lane_nan_quarantine_isolates_poisoned_lane(tmp_path, depth):
    """Acceptance: a lane-nan-poisoned lane fails ONLY its own record
    (structured nonfinite status + approximate step) while every
    co-scheduled lane's .npz output stays bit-identical to a clean run —
    at dispatch depths 0 (sync fallback) and 2 (pipelined)."""
    _, clean, _, _ = _run_wave(tmp_path, f"clean{depth}", QUAR_WAVE,
                               dispatch_depth=depth)
    eng, chaos, out, _ = _run_wave(tmp_path, f"chaos{depth}", QUAR_WAVE,
                                   dispatch_depth=depth,
                                   inject="lane-nan@10:req=r1")
    bad = chaos["r1"]
    assert bad["status"] == "nonfinite"
    assert "non-finite field detected at ~step" in bad["error"]
    assert not (out / "r1.npz").exists()  # a NaN field never persists
    assert eng.lanes_quarantined == 1 and eng.summary()["nonfinite"] == 1
    for rid in ("r0", "r2", "r3"):
        assert chaos[rid]["status"] == "ok"
        with np.load(out / f"{rid}.npz") as z:
            np.testing.assert_array_equal(z["T"], clean[rid]["T"])
    # and the healthy lanes equal their solo runs too (not just clean-run
    # equal: the quarantine must not perturb the masking contract)
    np.testing.assert_array_equal(chaos["r0"]["T"], solve(QUAR_WAVE[0]).T)


@pytest.mark.parametrize("depth", [0, 2])
def test_serve_on_nan_rollback_recovers_transient_poison(tmp_path, depth):
    """Acceptance: --serve-on-nan rollback restores the flagged lane's
    last verified-finite boundary and re-steps it ALONE; the lane-nan
    injection fires once per request, so the re-step is clean and the
    final field is bit-identical to an unpoisoned run."""
    eng, recs, _, _ = _run_wave(tmp_path, f"rb{depth}", QUAR_WAVE,
                                dispatch_depth=depth, on_nan="rollback",
                                inject="lane-nan@10:req=r1")
    assert eng.rollbacks == 1 and eng.lanes_quarantined == 0
    for i, cfg in enumerate(QUAR_WAVE):
        assert recs[f"r{i}"]["status"] == "ok", recs[f"r{i}"]
        np.testing.assert_array_equal(recs[f"r{i}"]["T"], solve(cfg).T)


def test_rollback_deterministic_blowup_quarantines_after_budget(tmp_path):
    """A genuinely unstable request (sigma far past the FTCS bound)
    re-flags after every rollback: the bounded retry budget must declare
    it deterministic and quarantine it, while its lane-mate finishes."""
    cfgs = [HeatConfig(n=16, ntime=200, dtype="float32", sigma=9.0),
            HeatConfig(n=16, ntime=40, dtype="float32")]
    eng, recs, _, _ = _run_wave(tmp_path, "boom", cfgs, on_nan="rollback")
    assert recs["r0"]["status"] == "nonfinite"
    assert "after 2 rollbacks (deterministic blow-up)" in recs["r0"]["error"]
    assert eng.rollbacks == 2 and eng.lanes_quarantined == 1
    assert recs["r1"]["status"] == "ok"
    np.testing.assert_array_equal(
        np.asarray(recs["r1"]["T"], np.float32),
        np.asarray(solve(cfgs[1]).T, np.float32))


def test_boundary_vector_carries_per_lane_finite_bits():
    """Engine-level unit: the chunk program's (K_BOUNDARY, L) boundary
    vector flags exactly the poisoned lane — no extra D2H beyond the
    boundary fetch the scheduler already pays. Rows 2+ carry the
    bitcast numerics stats (ISSUE 15); rows 0-1 stay the int32
    remaining/finite contract this test pins."""
    key = BucketKey(2, 16, "float64", "edges")
    eng = LaneEngine(key, 2, 4)
    from heat_tpu.grid import initial_condition

    cfg = HeatConfig(n=16, ntime=8, dtype="float64")
    for lane in (0, 1):
        eng.load_lane(lane, initial_condition(cfg), cfg.r, 8, cfg.bc_value)
    eng.poison_lane(0, cfg.n)
    b = eng.step_chunk()
    assert b.shape == (engine_mod.K_BOUNDARY, 2)
    assert list(b[0]) == [4, 4]        # remaining: both stepped the chunk
    assert list(b[1]) == [0, 1]        # finite bits: only lane 0 flagged


def test_deadline_sheds_queued_request_without_admitting():
    """A queued request already past its deadline is shed at admission
    time (status deadline, 'never admitted') — it must not occupy a lane
    for a result nobody is waiting for. The lane-holder is unaffected."""
    eng = Engine(quiet(lanes=1, chunk=8, buckets=(16,)))
    slow = eng.submit(HeatConfig(n=16, ntime=40, dtype="float64"))
    doomed = eng.submit(HeatConfig(n=16, ntime=40, dtype="float64"),
                        deadline_ms=1e-6)
    recs = {r["id"]: r for r in eng.results()}
    assert recs[slow]["status"] == "ok"
    assert recs[doomed]["status"] == "deadline"
    assert "never admitted" in recs[doomed]["error"]
    assert eng.deadline_misses == 1


def test_deadline_preempts_running_lane_at_boundary(monkeypatch):
    """A lane whose request blows its deadline mid-flight is preempted at
    its NEXT chunk boundary (status deadline, approximate step count) and
    the freed lane admits the next queued request. Driven by a fake wall
    clock (1 s per reading) so the preemption step is deterministic-ish
    and the test never sleeps."""
    import heat_tpu.serve.scheduler as sched_mod

    t = {"now": 0.0}

    def fake_clock():
        t["now"] += 1.0
        return t["now"]

    monkeypatch.setattr(sched_mod, "wall_clock", fake_clock)
    eng = Engine(quiet(lanes=1, chunk=8, buckets=(16,)))
    # 20 s budget: survives admission (a few clock reads) but not the
    # per-boundary reads of a 10-chunk solve
    doomed = eng.submit(HeatConfig(n=16, ntime=80, dtype="float64"),
                        deadline_ms=20_000.0)
    follower = eng.submit(HeatConfig(n=16, ntime=8, dtype="float64"))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[doomed]["status"] == "deadline"
    assert "preempted at the chunk boundary" in recs[doomed]["error"]
    assert recs[follower]["status"] == "ok"  # freed lane kept serving
    assert eng.deadline_misses == 1


def test_engine_default_deadline_and_per_request_override():
    """ServeConfig.deadline_ms is the engine default; a request's own
    deadline_ms overrides it in both directions."""
    eng = Engine(quiet(lanes=2, chunk=4, buckets=(16,), deadline_ms=1e-6))
    doomed = eng.submit(HeatConfig(n=8, ntime=4, dtype="float64"))
    saved = eng.submit(HeatConfig(n=8, ntime=4, dtype="float64"),
                       deadline_ms=120_000.0)
    recs = {r["id"]: r for r in eng.results()}
    assert recs[doomed]["status"] == "deadline"
    assert recs[saved]["status"] == "ok"
    assert recs[saved]["deadline_ms"] == 120_000.0


def test_max_queue_sheds_with_structured_overloaded_rejection():
    eng = Engine(quiet(lanes=1, chunk=4, buckets=(16,), max_queue=2))
    rids = [eng.submit(HeatConfig(n=8, ntime=4, dtype="float64"))
            for _ in range(5)]
    recs = {r["id"]: r for r in eng.results()}
    statuses = [recs[r]["status"] for r in rids]
    assert statuses == ["ok", "ok", "rejected", "rejected", "rejected"]
    for r in rids[2:]:
        assert "overloaded" in recs[r]["error"]
    assert eng.shed == 3 and eng.summary()["shed"] == 3
    # the shed requests never held a lane or a queue slot: a later wave
    # still serves (the guardrail protects the engine, not one batch)
    again = eng.submit(HeatConfig(n=8, ntime=4, dtype="float64"))
    assert {r["id"]: r for r in eng.results()}[again]["status"] == "ok"


def test_fetch_watchdog_fails_group_cleanly_others_drain(tmp_path):
    """Acceptance: a fetch-hang beyond the watchdog fails the affected
    GROUP's requests with structured records — and the engine still
    returns a record for every request, with other bucket groups
    draining normally (no hang)."""
    faults.reset()
    eng = Engine(quiet(lanes=2, chunk=8, buckets=(16,),
                       inject="fetch-hang:ms=1500", fetch_timeout_s=0.2,
                       flight_dir=str(tmp_path)))
    # f64 group submitted first -> its boundary fetch comes first -> hangs
    hung = [eng.submit(HeatConfig(n=16, ntime=24, dtype="float64"))
            for _ in range(3)]
    fine = [eng.submit(HeatConfig(n=16, ntime=24, dtype="float32"))
            for _ in range(2)]
    recs = {r["id"]: r for r in eng.results()}
    assert len(recs) == 5  # a record for EVERY request — nothing dropped
    for rid in hung:
        assert recs[rid]["status"] == "error"
        assert "fetch-watchdog" in recs[rid]["error"]
    for rid in fine:
        assert recs[rid]["status"] == "ok"
    assert eng.watchdog_fired == 1


def test_fetch_watchdog_fires_in_sync_fallback_too(tmp_path):
    faults.reset()
    eng = Engine(quiet(lanes=1, chunk=8, buckets=(16,), dispatch_depth=0,
                       inject="fetch-hang:ms=1500", fetch_timeout_s=0.2,
                       flight_dir=str(tmp_path)))
    rid = eng.submit(HeatConfig(n=16, ntime=24, dtype="float64"))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[rid]["status"] == "error"
    assert "fetch-watchdog" in recs[rid]["error"]


def test_drain_on_exception_no_orphan_tmp_and_error_surfaces(tmp_path):
    """Satellite: an exception mid-Engine.run must still drain the
    SnapshotWriter — the already-finished request's file lands, no
    orphan *.tmp remains, and the scheduler's error (not a writer
    artifact) is what propagates."""
    out = tmp_path / "results"
    eng = Engine(quiet(lanes=1, chunk=4, buckets=(16,), out_dir=str(out)))
    done = eng.submit(HeatConfig(n=16, ntime=4, dtype="float64"))
    eng.submit(HeatConfig(n=16, ntime=400, dtype="float64"))
    real = LaneEngine.dispatch_chunk
    calls = {"n": 0}

    def flaky_dispatch(self, k=None):
        calls["n"] += 1
        if calls["n"] > 4:
            raise RuntimeError("chaos: device fell over mid-run")
        return real(self, k)

    try:
        LaneEngine.dispatch_chunk = flaky_dispatch
        with pytest.raises(RuntimeError, match="device fell over"):
            eng.run()
    finally:
        LaneEngine.dispatch_chunk = real
    recs = {r["id"]: r for r in eng._records}
    assert recs[done]["status"] == "ok"
    assert (out / f"{done}.npz").exists()      # writer drained, not killed
    assert not list(out.glob("*.tmp"))         # no torn temp files


def test_serve_config_validates_fault_domain_knobs():
    with pytest.raises(ValueError, match="on_nan"):
        ServeConfig(on_nan="retry")
    with pytest.raises(ValueError, match="deadline_ms"):
        ServeConfig(deadline_ms=0)
    with pytest.raises(ValueError, match="max_queue"):
        ServeConfig(max_queue=-1)
    with pytest.raises(ValueError, match="fetch_timeout_s"):
        ServeConfig(fetch_timeout_s=0)
    with pytest.raises(ValueError, match="unknown fault kind"):
        ServeConfig(inject="bogus@3")
    eng = Engine(quiet())
    with pytest.raises(ValueError, match="deadline_ms"):
        eng.submit(HeatConfig(n=8, ntime=1), deadline_ms=-5)


def test_summary_and_timing_surface_fault_domain_counters(tmp_path):
    eng, _, _, _ = _run_wave(tmp_path, "counters", QUAR_WAVE,
                             inject="lane-nan@10:req=r1")
    s = eng.summary()
    assert s["lanes_quarantined"] == 1
    assert set(s) >= {"rollbacks", "deadline_misses", "shed",
                      "watchdog_fired"}
    assert eng.timing.lanes_quarantined == 1
    assert any("serve faults" in l for l in eng.timing.report_lines())


def test_config_from_request_accepts_scheduler_keys():
    cfg = config_from_request({"id": "x", "n": 16, "ntime": 4,
                               "deadline_ms": 2000})
    assert cfg.n == 16  # deadline_ms is the scheduler's, not physics


def test_serve_jsonl_deadline_ms_field(tmp_path):
    from heat_tpu.serve.api import load_requests

    p = tmp_path / "reqs.jsonl"
    p.write_text('{"id": "a", "n": 16, "ntime": 4, "deadline_ms": 2000}\n'
                 '{"id": "b", "n": 16, "ntime": 4}\n'
                 '{"id": "c", "n": 16, "ntime": 4, "deadline_ms": -3}\n')
    rows = load_requests(p)
    assert rows[0].id == "a" and rows[0].deadline_ms == 2000.0
    assert rows[0].error is None
    assert rows[1].deadline_ms is None
    assert rows[2].cfg is None and "deadline_ms" in rows[2].error


def test_serve_jsonl_tenant_and_class_fields(tmp_path):
    """tenant/class are scheduler fields validated in config.py: good
    values parse through, a typoed class is a per-line rejection."""
    from heat_tpu.serve.api import load_requests

    p = tmp_path / "reqs.jsonl"
    p.write_text(
        '{"id": "a", "n": 16, "ntime": 4, "tenant": "acme", '
        '"class": "interactive"}\n'
        '{"id": "b", "n": 16, "ntime": 4}\n'
        '{"id": "c", "n": 16, "ntime": 4, "class": "premium"}\n'
        '{"id": "d", "n": 16, "ntime": 4, "tenant": "bad tenant!"}\n')
    rows = load_requests(p)
    assert rows[0].tenant == "acme" and rows[0].slo_class == "interactive"
    assert rows[1].tenant == "default" and rows[1].slo_class == "standard"
    assert rows[2].cfg is None and "class" in rows[2].error
    assert rows[3].cfg is None and "tenant" in rows[3].error


def test_serve_cli_fault_domain_flags(tmp_cwd, capsys):
    from heat_tpu.cli import main

    reqs = tmp_cwd / "reqs.jsonl"
    reqs.write_text(
        '{"id": "good", "n": 16, "ntime": 24, "dtype": "float64"}\n'
        '{"id": "bad", "n": 16, "ntime": 24, "dtype": "float64"}\n')
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "16",
               "--chunk", "8", "--inject", "lane-nan@10:req=bad",
               "--json"])
    out = capsys.readouterr().out
    assert rc == 1  # a quarantined request is a nonzero exit
    records = {r["id"]: r for r in
               (json.loads(l) for l in out.splitlines()
                if l.startswith("{") and '"serve_request"' in l)}
    assert records["bad"]["status"] == "nonfinite"
    assert records["good"]["status"] == "ok"
    assert "fault domains: 1 quarantined" in out
    summary = json.loads([l for l in out.splitlines()
                          if '"lanes_quarantined"' in l][-1])
    assert summary["lanes_quarantined"] == 1
    # rollback mode recovers the same wave (lane-nan fires once/request)
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "16",
               "--chunk", "8", "--inject", "lane-nan@10:req=bad",
               "--serve-on-nan", "rollback"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "2 ok" in out and "1 rollback(s)" in out


def test_serve_cli_max_queue_and_deadline(tmp_cwd, capsys):
    from heat_tpu.cli import main

    reqs = tmp_cwd / "reqs.jsonl"
    reqs.write_text("".join(
        f'{{"id": "r{i}", "n": 16, "ntime": 8, "dtype": "float64"}}\n'
        for i in range(4)))
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "16",
               "--chunk", "4", "--max-queue", "2"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "2 ok" in out and "2 rejected" in out and "2 shed" in out
    # a sub-millisecond engine-default deadline sheds everything
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "16",
               "--chunk", "4", "--serve-deadline", "0.0001"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "4 deadline miss(es)" in out
