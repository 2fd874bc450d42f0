"""Pallas-native lane stepping (ISSUE 9): the serve chunk program's two
interchangeable bodies.

The load-bearing contract is the ORACLE relation: the multi-lane Pallas
kernel family (``ops/pallas_stencil.lane_multistep`` — lane axis as a
grid dimension, per-lane mask + countdown gate + isfinite reduction
fused into the stencil pass) must produce, for every request, the exact
bytes the vmapped masked XLA lane program produces — across dtypes,
dimensionality, BCs, dispatch depths, mid-flight admits, lane-tier
growth, and the whole fault-domain repertoire (quarantine, rollback
heal, watchdog). Pallas runs in interpret mode on CPU, so the matrix is
tier-1. The second contract is fallback HONESTY: an unsupported
(bucket, dtype) under a forced/auto Pallas request degrades to XLA as a
structured ``lane_kernel_fallback`` record + counter — loudly, never an
error, never silently. Third: rollback mode dispatches NO standalone
full-stack copy program per chunk (the snapshot is the undonated input
stack itself)."""

import json

import numpy as np
import pytest

from heat_tpu.backends import solve
from heat_tpu.config import HeatConfig
from heat_tpu.runtime import faults
from heat_tpu.serve import Engine, ServeConfig
from heat_tpu.serve.engine import BucketKey, resolve_lane_kernel


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


def quiet(**kw) -> ServeConfig:
    kw.setdefault("emit_records", False)
    kw.setdefault("keep_fields", True)
    return ServeConfig(**kw)


def drain(reqs, **kw):
    """Drain ``reqs`` through one engine; records in submit order."""
    eng = Engine(quiet(**kw))
    ids = [eng.submit(cfg) for cfg in reqs]
    by_id = {r["id"]: r for r in eng.results()}
    return eng, [by_id[i] for i in ids]


def assert_byte_identical(recs_a, recs_b):
    for a, b in zip(recs_a, recs_b):
        assert a["status"] == b["status"], (a, b)
        if a["status"] == "ok":
            assert a["T"].dtype == b["T"].dtype
            assert a["T"].tobytes() == b["T"].tobytes(), a["id"]


# --- the bit-identity matrix -------------------------------------------------
#
# Not a full cross-product (each cell compiles interpret-mode Pallas
# programs); the cells below collectively cover {f32, bf16, f64-fallback}
# x {2D, 3D} x {ghost, edges} x dispatch depths {0, 2}, with 5 requests
# over 2 lanes forcing mid-flight admits in every cell.

MATRIX = [
    # (ndim, dtype, bc mix, depth)
    (2, "float32", ("ghost", "edges"), 2),
    (2, "float32", ("edges", "ghost"), 0),
    (2, "bfloat16", ("ghost", "edges"), 2),
    (2, "float64", ("edges", "ghost"), 2),   # fallback path: still exact
    (3, "float32", ("ghost", "edges"), 2),
    (3, "float32", ("edges", "ghost"), 0),
]


def matrix_requests(ndim, dtype, bcs):
    small = 6 if ndim == 3 else 8
    big = 8 if ndim == 3 else 12
    return [
        HeatConfig(n=big, ntime=13, ndim=ndim, dtype=dtype, bc=bcs[0],
                   ic="hat"),
        HeatConfig(n=small, ntime=21, ndim=ndim, dtype=dtype, bc=bcs[1],
                   ic="uniform", nu=0.1),
        HeatConfig(n=big - 2, ntime=5, ndim=ndim, dtype=dtype, bc=bcs[0],
                   ic="hat_small"),
        HeatConfig(n=big, ntime=0, ndim=ndim, dtype=dtype, bc=bcs[1],
                   ic="hat"),
        HeatConfig(n=small + 1, ntime=30, ndim=ndim, dtype=dtype,
                   bc=bcs[0], ic="hat_half", bc_value=2.5),
    ]


@pytest.mark.parametrize("ndim,dtype,bcs,depth", MATRIX)
def test_pallas_lane_program_byte_identical_to_xla(ndim, dtype, bcs, depth):
    reqs = matrix_requests(ndim, dtype, bcs)
    bucket = (8,) if ndim == 3 else (12,)
    kw = dict(lanes=2, chunk=4, buckets=bucket, dispatch_depth=depth)
    eng_x, recs_x = drain(reqs, lane_kernel="xla", **kw)
    eng_p, recs_p = drain(reqs, lane_kernel="pallas", **kw)
    assert all(r["status"] == "ok" for r in recs_x)
    assert_byte_identical(recs_x, recs_p)
    if dtype == "float64":
        # no f64 lane kernel: forced pallas degrades per (bucket, tier)
        assert eng_p.lane_kernel_fallbacks >= 1
    else:
        assert eng_p.lane_kernel_fallbacks == 0
    # both engines also match the solo oracle (same-dtype CPU contract)
    for r, cfg in zip(recs_x, reqs):
        if r["status"] == "ok":
            assert np.array_equal(r["T"], solve(cfg).T)


def test_pallas_npz_outputs_byte_identical_across_depths(tmp_path):
    """The acceptance spelling: published npz files from
    --serve-lane-kernel pallas and xla are byte-identical at dispatch
    depths 0 and 2."""
    reqs = matrix_requests(2, "float32", ("ghost", "edges"))
    for depth in (0, 2):
        outs = {}
        for kernel in ("xla", "pallas"):
            d = tmp_path / f"{kernel}-{depth}"
            _, recs = drain(reqs, lane_kernel=kernel, lanes=2, chunk=4,
                            buckets=(12,), dispatch_depth=depth,
                            out_dir=str(d))
            outs[kernel] = d
        for p in sorted(outs["xla"].glob("*.npz")):
            q = outs["pallas"] / p.name
            with np.load(p) as a, np.load(q) as b:
                assert a["T"].dtype == b["T"].dtype
                assert a["T"].tobytes() == b["T"].tobytes(), p.name


def test_lane_tier_growth_under_pallas_stays_exact(monkeypatch):
    """Online admission outgrows the born tier: the grown Pallas group
    transplants occupants bit-exactly (padded-slab crop + reload). The
    scheduler is held right after the first request fills its tier-1
    group, and the rest queue before it goes on, so the group grows
    whatever the host's speed."""
    import threading

    from heat_tpu.serve import scheduler as sched

    fill = sched._GroupRunner._fill
    formed, go = threading.Event(), threading.Event()

    def held_fill(self):
        fill(self)
        if not formed.is_set() and any(o is not None for o in self.occupant):
            assert self.lanes == 1       # the born tier
            formed.set()
            go.wait(60)

    monkeypatch.setattr(sched._GroupRunner, "_fill", held_fill)
    results = {}
    for kernel in ("xla", "pallas"):
        formed.clear()
        go.clear()
        eng = Engine(quiet(lanes=4, chunk=4, buckets=(12,),
                           lane_kernel=kernel))
        eng.start()
        try:
            first = eng.submit(HeatConfig(n=10, ntime=40, dtype="float32",
                                          bc="ghost"))
            assert formed.wait(60)       # a tier-1 group holds `first`
            rest = [eng.submit(HeatConfig(n=8 + (i % 3), ntime=20 + 4 * i,
                                          dtype="float32", bc="ghost",
                                          ic="hat_small"))
                    for i in range(5)]
            go.set()
            recs = [eng.wait(rid, timeout=60)
                    for rid in [first] + rest]
        finally:
            go.set()
            eng.shutdown(timeout=60)
        assert all(r is not None and r["status"] == "ok" for r in recs)
        results[kernel] = (eng, [eng._by_id[r["id"]].get("T")
                                 for r in recs])
    assert results["xla"][0].lane_grows >= 1
    assert results["pallas"][0].lane_grows >= 1
    for a, b in zip(results["xla"][1], results["pallas"][1]):
        assert a is not None and b is not None
        assert a.tobytes() == b.tobytes()


# --- fault domains on the Pallas kernel --------------------------------------


CHAOS_REQS = [
    HeatConfig(n=10, ntime=12, dtype="float32", bc="ghost"),
    HeatConfig(n=12, ntime=20, dtype="float32", bc="edges",
               ic="hat_small"),
    HeatConfig(n=8, ntime=16, dtype="float32", bc="ghost", ic="uniform"),
]


@pytest.mark.parametrize("depth", [0, 2])
def test_quarantine_isolates_poisoned_lane_on_pallas(depth):
    ids = [f"r{i}" for i in range(len(CHAOS_REQS))]
    kw = dict(lanes=2, chunk=4, buckets=(12,), dispatch_depth=depth,
              lane_kernel="pallas")

    def run(inject):
        eng = Engine(quiet(inject=inject, **kw))
        for rid, cfg in zip(ids, CHAOS_REQS):
            eng.submit(cfg, request_id=rid)
        by_id = {r["id"]: r for r in eng.results()}
        return eng, [by_id[i] for i in ids]

    eng, recs = run("lane-nan@6:req=r1")
    assert [r["status"] for r in recs] == ["ok", "nonfinite", "ok"]
    assert eng.lanes_quarantined == 1
    _, clean = run("")
    for i in (0, 2):   # co-scheduled lanes bit-identical to a clean run
        assert recs[i]["T"].tobytes() == clean[i]["T"].tobytes()


@pytest.mark.parametrize("depth", [0, 2])
def test_rollback_heals_transient_poison_on_pallas(depth):
    kw = dict(lanes=2, chunk=4, buckets=(12,), dispatch_depth=depth,
              lane_kernel="pallas")
    eng = Engine(quiet(on_nan="rollback", inject="lane-nan@6:req=r1",
                       **kw))
    for i, cfg in enumerate(CHAOS_REQS):
        eng.submit(cfg, request_id=f"r{i}")
    by_id = {r["id"]: r for r in eng.results()}
    assert all(by_id[f"r{i}"]["status"] == "ok" for i in range(3))
    assert eng.rollbacks >= 1
    _, clean = drain(CHAOS_REQS, **kw)
    for i in range(3):   # healed bit-identically
        assert by_id[f"r{i}"]["T"].tobytes() == clean[i]["T"].tobytes()


def test_fetch_watchdog_fails_group_cleanly_on_pallas(tmp_path):
    eng = Engine(quiet(lanes=2, chunk=4, buckets=(12,), dispatch_depth=2,
                       lane_kernel="pallas", fetch_timeout_s=0.2,
                       inject="fetch-hang@2:ms=60000",
                       flight_dir=str(tmp_path)))
    for i, cfg in enumerate(CHAOS_REQS):
        eng.submit(cfg, request_id=f"r{i}")
    records = eng.results()   # must return, not hang
    assert eng.watchdog_fired == 1
    assert all(r["status"] in ("ok", "error") for r in records)
    assert any(r["status"] == "error"
               and "fetch-watchdog" in (r["error"] or "")
               for r in records)


# --- rollback copy tax (the second tentpole half) ----------------------------


def test_rollback_mode_dispatches_no_standalone_copy_program(monkeypatch):
    """Acceptance: --serve-on-nan rollback keeps every in-flight boundary
    restorable WITHOUT a per-chunk full-stack copy — the boundary
    snapshot is the undonated input stack itself. device_snapshot (the
    old copy program) must never run on the dispatch path, on either
    kernel; results stay byte-identical to on-nan=fail."""
    from heat_tpu.runtime import async_io

    calls = {"n": 0}
    real = async_io.device_snapshot

    def spy(T):
        calls["n"] += 1
        return real(T)

    monkeypatch.setattr(async_io, "device_snapshot", spy)
    reqs = matrix_requests(2, "float32", ("ghost", "edges"))
    for kernel in ("xla", "pallas"):
        calls["n"] = 0
        eng, recs = drain(reqs, lanes=2, chunk=4, buckets=(12,),
                          dispatch_depth=2, on_nan="rollback",
                          lane_kernel=kernel)
        assert eng.chunks_dispatched > 0
        assert calls["n"] == 0, (kernel, calls)
        _, plain = drain(reqs, lanes=2, chunk=4, buckets=(12,),
                         dispatch_depth=2, lane_kernel=kernel)
        assert_byte_identical(plain, recs)


def test_rollback_snapshot_survives_later_admissions():
    """The aliasing hazard the donate=False contract exists for: a lane
    swap (admission) after a snapshot was taken must not invalidate the
    snapshot another lane's rollback later restores from. 3 requests
    over 1 lane force admissions between boundaries; the poisoned
    request must still heal from a last-good snapshot."""
    eng = Engine(quiet(lanes=1, chunk=4, buckets=(12,),
                       dispatch_depth=2, on_nan="rollback",
                       lane_kernel="pallas",
                       inject="lane-nan@6:req=r1"))
    cfgs = [HeatConfig(n=10, ntime=12, dtype="float32", bc="ghost"),
            HeatConfig(n=10, ntime=12, dtype="float32", bc="ghost",
                       ic="hat_small"),
            HeatConfig(n=10, ntime=12, dtype="float32", bc="ghost",
                       ic="uniform")]
    for i, cfg in enumerate(cfgs):
        eng.submit(cfg, request_id=f"r{i}")
    by_id = {r["id"]: r for r in eng.results()}
    assert all(by_id[f"r{i}"]["status"] == "ok" for i in range(3))
    assert eng.rollbacks >= 1


# --- fallback honesty --------------------------------------------------------


def test_forced_pallas_on_f64_degrades_loudly_not_silently(capsys):
    eng = Engine(ServeConfig(lanes=2, chunk=4, buckets=(12,),
                             lane_kernel="pallas", emit_records=False,
                             keep_fields=True))
    eng.submit(HeatConfig(n=10, ntime=9, dtype="float64", bc="ghost"))
    (rec,) = eng.results()
    assert rec["status"] == "ok"          # never an error
    assert eng.lane_kernel_fallbacks == 1
    assert eng.summary()["lane_kernel_fallbacks"] == 1
    out = capsys.readouterr().out
    # the structured record names the bucket and the reason
    rows = [json.loads(line) for line in out.splitlines()
            if line.startswith("{")
            and json.loads(line).get("event") == "lane_kernel_fallback"]
    assert len(rows) == 1
    assert rows[0]["bucket"] == "2d/n12/float64/ghost"
    assert rows[0]["requested"] == "pallas"
    assert "f64" in rows[0]["reason"] or "float64" in rows[0]["reason"]


def test_fallback_deduped_per_bucket_tier_and_counted_per_tier():
    """Two f64 waves through one engine: the (bucket, tier) fallback is
    recorded once, not once per wave; a second bucket adds a second."""
    eng = Engine(quiet(lanes=2, chunk=4, buckets=(12, 16),
                       lane_kernel="pallas"))
    eng.submit(HeatConfig(n=10, ntime=5, dtype="float64"))
    eng.results()
    assert eng.lane_kernel_fallbacks == 1
    eng.submit(HeatConfig(n=10, ntime=5, dtype="float64", ic="uniform"))
    eng.results()   # warm re-run, same (bucket, tier)
    assert eng.lane_kernel_fallbacks == 1
    eng.submit(HeatConfig(n=14, ntime=5, dtype="float64"))
    eng.results()   # second bucket
    assert eng.lane_kernel_fallbacks == 2


def test_auto_never_errors_and_is_silent_off_tpu():
    """auto off-TPU resolves XLA as policy (no fallback record — nothing
    degraded); every dtype serves."""
    reqs = [HeatConfig(n=10, ntime=8, dtype=d)
            for d in ("float32", "float64", "bfloat16")]
    eng, recs = drain(reqs, lanes=2, chunk=4, buckets=(12,),
                      lane_kernel="auto")
    assert all(r["status"] == "ok" for r in recs)
    assert eng.lane_kernel_fallbacks == 0


def test_resolve_lane_kernel_rules(monkeypatch):
    key32 = BucketKey(2, 12, "float32", "ghost")
    key64 = BucketKey(2, 12, "float64", "ghost")
    assert resolve_lane_kernel("xla", key32) == ("xla", None)
    assert resolve_lane_kernel("pallas", key32) == ("pallas", None)
    k, reason = resolve_lane_kernel("pallas", key64)
    assert k == "xla" and reason is not None
    # auto off-TPU: XLA, no reason (policy, not degradation)
    assert resolve_lane_kernel("auto", key32) == ("xla", None)
    # auto on (faked) TPU: pallas where a plan exists, loud elsewhere
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_lane_kernel("auto", key32) == ("pallas", None)
    k, reason = resolve_lane_kernel("auto", key64)
    assert k == "xla" and reason is not None


def test_metrics_surface_lane_kernel_gauge_and_cost_kernel_label():
    from heat_tpu.serve.gateway import render_metrics

    eng, _ = drain([HeatConfig(n=10, ntime=8, dtype="float32",
                               bc="ghost")],
                   lanes=1, chunk=4, buckets=(12,), lane_kernel="pallas")
    text = render_metrics(eng)
    assert "heat_tpu_serve_lane_kernel_fallbacks_total" in text
    assert 'kernel="pallas"' in text   # cost rows carry the kernel key


def test_serve_config_validates_lane_kernel():
    with pytest.raises(ValueError, match="lane_kernel"):
        ServeConfig(lane_kernel="mosaic")
    for v in ("auto", "pallas", "xla"):
        assert ServeConfig(lane_kernel=v).lane_kernel == v


def test_serve_cli_lane_kernel_flag(tmp_path, capsys, monkeypatch):
    from heat_tpu.cli import main

    monkeypatch.chdir(tmp_path)
    req = tmp_path / "reqs.jsonl"
    req.write_text(json.dumps({"n": 10, "ntime": 6, "dtype": "float32",
                               "bc": "ghost"}) + "\n")
    rc = main(["serve", "--requests", str(req), "--lanes", "1",
               "--chunk", "4", "--buckets", "12",
               "--serve-lane-kernel", "pallas"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "lane kernel pallas" in out
    # bad value is an argparse rejection
    with pytest.raises(SystemExit):
        main(["serve", "--requests", str(req),
              "--serve-lane-kernel", "mosaic"])


def test_lane_kernel_lab_harness_smoke(tmp_path):
    """The three-way A/B harness runs end-to-end on a tiny 2-lane
    workload and emits every field the committed artifact and perfcheck
    rely on (speed not asserted — plumbing, not perf)."""
    import importlib.util
    import sys
    from pathlib import Path

    bench_dir = Path(__file__).resolve().parent.parent / "benchmarks"
    sys.path.insert(0, str(bench_dir))
    try:
        spec = importlib.util.spec_from_file_location(
            "lane_lab_smoke", bench_dir / "serve_lane_kernel_lab.py")
        lab = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lab)
        out = tmp_path / "lane_lab.json"
        rc = lab.main(["--requests", "4", "--lanes", "2", "--chunk", "8",
                       "--out", str(out)])
    finally:
        sys.path.remove(str(bench_dir))
    assert rc == 0
    rec = json.loads(out.read_text())
    assert rec["bench"] == "serve_lane_kernel_lab"
    assert rec["bit_identical"] is True
    assert rec["solo_sample_identical"] is True
    assert rec["zero_fallbacks"] is True
    for side, kern in (("pallas", "pallas"), ("xla", "xla")):
        assert rec[side]["ok"] == 4
        assert rec[side]["lane_kernel_fallbacks"] == 0
        rows = rec[side]["cost_model"]
        assert rows and all(e["kernel"] == kern for e in rows)
    assert rec["pallas_vs_xla"] is not None
    assert rec["pallas_vs_solo"] is not None
    assert rec["solo_pallas"]["points_per_s"] > 0
