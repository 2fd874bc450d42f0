"""Two-tier placement (ISSUE 10): sharded mega-lanes for bucket-overflow
requests, co-scheduled with packed vmapped lanes.

The load-bearing contracts:

- a request whose side overflows every bucket is ADMITTED as a
  mesh-spanning sharded mega-lane on a multi-device host — and its
  result (in-memory field and npz payload) is byte-identical to a solo
  ``drive()`` on the sharded backend of the same config, at dispatch
  depths 0 and 2;
- packed-lane traffic co-scheduled with a resident mega-lane stays
  byte-identical to a mega-free run (placement never perturbs physics);
- ``--mega-lanes 0`` (and single-device hosts under auto) restore the
  PR-5 bucket-overflow rejection bit-identically, now enriched with the
  mesh capacity ceiling and a machine-readable ``hint``;
- the mega-lane is a full fault domain: deadline preemption, lane-nan
  quarantine, ``--serve-on-nan rollback`` recovery, and the
  boundary-fetch watchdog all behave like a packed group of lane-count
  one-mesh;
- every surface (records, cost model, /metrics, /v1/usage) carries the
  ``placement=packed|mega`` dimension.

The 8-virtual-CPU-device harness (tests/conftest.py) is the mesh."""

import json

import numpy as np
import pytest

from heat_tpu.backends import solve
from heat_tpu.config import HeatConfig, parse_mega_lanes
from heat_tpu.runtime import faults
from heat_tpu.serve import Engine, ServeConfig
from heat_tpu.serve import scheduler as sched_mod


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


def quiet(**kw) -> ServeConfig:
    kw.setdefault("emit_records", False)
    kw.setdefault("lanes", 2)
    kw.setdefault("chunk", 8)
    kw.setdefault("buckets", (8,))
    return ServeConfig(**kw)


# n=16 overflows the (8,) bucket table and divides the auto 4x2 mesh of
# the 8-device harness; smalls pack into the 8-bucket as usual
MEGA_CFG = HeatConfig(n=16, ntime=37, dtype="float64", bc="edges")
SMALLS = [HeatConfig(n=8, ntime=20, dtype="float64"),
          HeatConfig(n=8, ntime=11, dtype="float64", nu=0.1,
                     bc="ghost", ic="uniform")]


def solo_sharded(cfg):
    return solve(cfg.with_(backend="sharded")).T


# --- overflow -> mega admission + bit-identity -------------------------------


@pytest.mark.parametrize("depth", [0, 2])
def test_mega_lane_bit_identical_to_solo_sharded_drive(tmp_path, depth):
    """Acceptance: the previously-rejected oversized request completes
    as a mega-lane whose npz payload is byte-identical to a solo sharded
    drive(), while co-scheduled packed lanes stay byte-identical to a
    mega-free run — at dispatch depths 0 and 2."""
    # mega-free reference drain of the same smalls
    free = Engine(quiet(dispatch_depth=depth))
    free_ids = [free.submit(c) for c in SMALLS]
    free_recs = {r["id"]: r for r in free.results()}

    out = tmp_path / f"mega{depth}"
    eng = Engine(quiet(dispatch_depth=depth, out_dir=str(out),
                       keep_fields=True))
    big = eng.submit(MEGA_CFG)
    ids = [eng.submit(c) for c in SMALLS]
    recs = {r["id"]: r for r in eng.results()}

    assert recs[big]["status"] == "ok", recs[big]
    assert recs[big]["placement"] == "mega"
    assert recs[big]["bucket"] is None
    solo = solo_sharded(MEGA_CFG)
    np.testing.assert_array_equal(recs[big]["T"], solo)
    # the persisted npz payload too (same writer as packed results)
    with np.load(out / f"{big}.npz") as z:
        assert z["T"].dtype == solo.dtype
        assert z["T"].tobytes() == solo.tobytes()
        assert int(z["step"]) == MEGA_CFG.ntime
    # co-scheduled packed lanes == the mega-free run, byte for byte
    for fid, rid, cfg in zip(free_ids, ids, SMALLS):
        assert recs[rid]["status"] == "ok"
        assert recs[rid]["placement"] == "packed"
        np.testing.assert_array_equal(recs[rid]["T"], free_recs[fid]["T"])
        np.testing.assert_array_equal(recs[rid]["T"], solve(cfg).T)
    s = eng.summary()
    assert s["placement"] == {"mega": 1, "packed": len(SMALLS)}
    assert s["mega_lanes"] >= 1 and s["mega_compiles"] >= 1
    # the packed tier's compile accounting is untouched by the mega tier
    assert free.step_compiles == eng.step_compiles


def test_mega_warm_readmission_compiles_nothing():
    """Re-admitting the same oversized config reuses every cached mega
    program (machinery + chunk executables) — zero new compiles."""
    eng = Engine(quiet())
    eng.submit(MEGA_CFG)
    eng.results()
    warm = eng.mega_compiles
    assert warm >= 1
    rid = eng.submit(MEGA_CFG)
    recs = {r["id"]: r for r in eng.results()}
    assert recs[rid]["status"] == "ok"
    assert eng.mega_compiles == warm


def test_mega_ntime_zero_returns_ic():
    cfg = MEGA_CFG.with_(ntime=0)
    eng = Engine(quiet(keep_fields=True))
    rid = eng.submit(cfg)
    recs = {r["id"]: r for r in eng.results()}
    assert recs[rid]["status"] == "ok"
    np.testing.assert_array_equal(recs[rid]["T"], solo_sharded(cfg))


# --- rejection paths ---------------------------------------------------------


def test_single_device_auto_keeps_overflow_rejection(monkeypatch):
    """Auto --mega-lanes resolves 0 on a single-device host: overflow
    stays a rejection, now carrying the mesh capacity ceiling and the
    enable hint."""
    monkeypatch.setattr(sched_mod, "mega_device_count", lambda: 1)
    eng = Engine(quiet())
    big = eng.submit(MEGA_CFG)
    ok = eng.submit(SMALLS[0])
    recs = {r["id"]: r for r in eng.results()}
    assert recs[big]["status"] == "rejected"
    assert "bucket-overflow" in recs[big]["error"]
    assert "1-device" in recs[big]["error"]
    assert recs[big]["hint"] == "enable --mega-lanes"
    assert recs[big]["placement"] is None
    assert recs[ok]["status"] == "ok"


def test_mega_lanes_zero_restores_rejection_bit_identically():
    """--mega-lanes 0 is the pre-mega engine: overflow rejected (with
    the ceiling + hint), packed traffic byte-identical and admission
    trace unchanged vs an engine that never saw the overflow."""
    ref = Engine(quiet())
    ref_ids = [ref.submit(c) for c in SMALLS]
    ref_recs = {r["id"]: r for r in ref.results()}

    eng = Engine(quiet(mega_lanes=0))
    big = eng.submit(MEGA_CFG)
    ids = [eng.submit(c) for c in SMALLS]
    recs = {r["id"]: r for r in eng.results()}
    assert recs[big]["status"] == "rejected"
    assert "could serve it" in recs[big]["error"]  # the capacity ceiling
    assert recs[big]["hint"] == "enable --mega-lanes"
    for rid, fid in zip(ids, ref_ids):
        np.testing.assert_array_equal(recs[rid]["T"], ref_recs[fid]["T"])
    assert eng.admission_trace == [r for r in ids]
    assert eng.mega_compiles == 0 and eng.summary()["mega_lanes"] == 0


def test_mega_indivisible_side_rejected_with_constraint():
    """A side that does not shard evenly over the mesh is still a
    rejection — naming the mesh shape and the divisibility remedy."""
    eng = Engine(quiet())
    rid = eng.submit(HeatConfig(n=17, ntime=4, dtype="float64"))
    rec = {r["id"]: r for r in eng.results()}[rid]
    assert rec["status"] == "rejected"
    assert "does not divide evenly" in rec["error"]
    assert "hint" not in rec


def test_mega_queue_counts_against_max_queue():
    eng = Engine(quiet(mega_lanes=1, max_queue=1))
    first = eng.submit(SMALLS[0])
    shed = eng.submit(MEGA_CFG)
    recs = {r["id"]: r for r in eng.results()}
    assert recs[first]["status"] == "ok"
    assert recs[shed]["status"] == "rejected"
    assert "overloaded" in recs[shed]["error"]
    assert eng.shed == 1


# --- fault-domain parity -----------------------------------------------------


@pytest.mark.parametrize("depth", [0, 2])
def test_mega_lane_nan_quarantines_mesh_not_packed_lanes(tmp_path, depth):
    """A lane-nan-poisoned mega request fails with the structured
    nonfinite status (no npz persisted) while co-scheduled packed lanes
    drain bit-identically — the mega fault domain is one mesh."""
    out = tmp_path / f"q{depth}"
    eng = Engine(quiet(dispatch_depth=depth, out_dir=str(out),
                       keep_fields=True,
                       inject="lane-nan@10:req=boom"))
    big = eng.submit(MEGA_CFG, request_id="boom")
    small = eng.submit(SMALLS[0])
    recs = {r["id"]: r for r in eng.results()}
    assert recs[big]["status"] == "nonfinite"
    assert "mega lane" in recs[big]["error"]
    assert not (out / "boom.npz").exists()
    assert eng.lanes_quarantined == 1
    assert recs[small]["status"] == "ok"
    np.testing.assert_array_equal(recs[small]["T"], solve(SMALLS[0]).T)


@pytest.mark.parametrize("depth", [0, 2])
def test_mega_rollback_recovers_transient_poison(depth):
    """--serve-on-nan rollback restores the mega-lane's last verified
    boundary (or the IC) and re-steps the mesh; the one-shot poison
    leaves the final field bit-identical to a clean solo sharded run."""
    eng = Engine(quiet(dispatch_depth=depth, on_nan="rollback",
                       keep_fields=True, inject="lane-nan@10:req=heal"))
    big = eng.submit(MEGA_CFG, request_id="heal")
    recs = {r["id"]: r for r in eng.results()}
    assert recs[big]["status"] == "ok", recs[big]
    assert eng.rollbacks == 1 and eng.lanes_quarantined == 0
    np.testing.assert_array_equal(recs[big]["T"], solo_sharded(MEGA_CFG))


# float32 with the Pallas local kernel (interpret mode on the CPU): each
# shard rides in the thin-band kernel's aligned carry, alignment cells
# beyond its logical padded extent (backends/sharded.py carried_shapes)
ALIGNED_MEGA = HeatConfig(n=64, ntime=37, dtype="float32", bc="edges",
                          local_kernel="pallas")


def test_mega_poison_and_perturb_hit_the_owned_centre_of_aligned_carry():
    """On a 2x2 mesh whose shards ride in the aligned carry, the chaos
    injections land on the centre OWNED cell (not on a frozen alignment
    cell of a lower shard), and the next chunk's health bit sees it."""
    import jax

    from heat_tpu.backends.sharded import carried_shapes
    from heat_tpu.parallel.mesh import build_mesh
    from heat_tpu.serve.engine import MegaLaneEngine

    cfg = ALIGNED_MEGA
    mesh = build_mesh(2, (2, 2), devices=jax.devices()[:4])
    eng = MegaLaneEngine(cfg, mesh, chunk=8)
    logical, carried = carried_shapes(cfg, (2, 2), eng.kf)
    assert carried != logical
    clean = MegaLaneEngine.extract(eng.final_snapshot())
    eng.perturb_center(0.5)
    bumped = MegaLaneEngine.extract(eng.final_snapshot())
    diff = bumped != clean
    assert diff.sum() == 1 and diff[32, 32]
    assert bumped[32, 32] == np.float32(clean[32, 32] + np.float32(0.5))
    eng.poison_center()
    field = MegaLaneEngine.extract(eng.final_snapshot())
    assert np.isnan(field).sum() == 1 and np.isnan(field[32, 32])
    assert int(np.asarray(eng.dispatch_chunk(8))[1, 0]) == 0


def test_mega_lane_nan_quarantines_the_aligned_carry(tmp_path):
    """lane-nan on a mega request whose shards ride in the aligned carry
    (the auto 4x2 mesh) is seen and quarantined like the logical carry's."""
    from heat_tpu.backends.sharded import carried_shapes, fuse_depth_sharded

    kf = fuse_depth_sharded(ALIGNED_MEGA, (4, 2))
    logical, carried = carried_shapes(ALIGNED_MEGA, (4, 2), kf)
    assert carried != logical
    out = tmp_path / "aligned"
    eng = Engine(quiet(out_dir=str(out), inject="lane-nan@10:req=boom"))
    big = eng.submit(ALIGNED_MEGA, request_id="boom")
    recs = {r["id"]: r for r in eng.results()}
    assert recs[big]["status"] == "nonfinite", recs[big]
    assert "mega lane" in recs[big]["error"]
    assert not (out / "boom.npz").exists()
    assert eng.lanes_quarantined == 1


def test_mega_deadline_preempts_at_boundary(monkeypatch):
    """A mega request past its budget is preempted at its next chunk
    boundary (status deadline, partial usage billed) and the freed slot
    admits the next queued mega request (fake 1 s-per-reading clock)."""
    t = {"now": 0.0}

    def fake_clock():
        t["now"] += 1.0
        return t["now"]

    monkeypatch.setattr(sched_mod, "wall_clock", fake_clock)
    eng = Engine(quiet(mega_lanes=1))
    doomed = eng.submit(MEGA_CFG.with_(ntime=80), deadline_ms=20_000.0)
    follower = eng.submit(MEGA_CFG.with_(ntime=8))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[doomed]["status"] == "deadline"
    assert "mega lane preempted" in recs[doomed]["error"]
    assert recs[doomed]["usage"]["steps"] > 0
    assert recs[follower]["status"] == "ok"
    assert eng.deadline_misses == 1


def test_mega_watchdog_fails_tier_cleanly_packed_drains(tmp_path):
    """A wedged mega boundary fetch fails the mega tier's in-flight AND
    queued requests with structured records — and the packed group keeps
    draining (no hang, a record for every request). fetch index 0 is the
    packed group's (runners round-robin groups first), index 1 the
    mega-lane's."""
    eng = Engine(quiet(inject="fetch-hang@1:ms=1500", fetch_timeout_s=0.2,
                       flight_dir=str(tmp_path)))
    packed = eng.submit(SMALLS[0])
    hung = eng.submit(MEGA_CFG, request_id="wedge")
    queued = eng.submit(MEGA_CFG.with_(ntime=5), request_id="behind")
    recs = {r["id"]: r for r in eng.results()}
    assert len(recs) == 3
    for rid in (hung, queued):
        assert recs[rid]["status"] == "error"
        assert "fetch-watchdog" in recs[rid]["error"]
    assert recs[packed]["status"] == "ok"
    assert eng.watchdog_fired == 1


def test_mega_watchdog_sync_fallback(tmp_path):
    eng = Engine(quiet(dispatch_depth=0, inject="fetch-hang:ms=1500",
                       fetch_timeout_s=0.2, flight_dir=str(tmp_path)))
    rid = eng.submit(MEGA_CFG)
    recs = {r["id"]: r for r in eng.results()}
    assert recs[rid]["status"] == "error"
    assert "fetch-watchdog" in recs[rid]["error"]


# --- observability surfaces --------------------------------------------------


def test_metrics_usage_and_cost_model_carry_placement(tmp_path):
    from heat_tpu.serve.gateway import (render_metrics, render_statusz,
                                        usage_payload)

    eng = Engine(quiet())
    eng.submit(MEGA_CFG, tenant="acme")
    eng.submit(SMALLS[0], tenant="acme")
    eng.results()
    # cost-model rows keyed by placement (and the sharded mega kernel)
    rows = eng.summary()["cost_model"]
    placements = {(e["placement"], e["kernel"]) for e in rows}
    assert ("mega", "sharded") in placements
    assert any(p == "packed" for p, _ in placements)
    text = render_metrics(eng)
    assert 'heat_tpu_serve_requests_by_placement_total{placement="mega"} 1' \
        in text
    assert ('heat_tpu_serve_requests_by_placement_total'
            '{placement="packed"} 1') in text
    assert 'placement="mega"' in text.split(
        "heat_tpu_serve_cost_s_per_lane_step", 1)[1]
    assert "heat_tpu_serve_mega_lanes 1" in text
    # every sample line still parses as name{labels} value
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        float(line.rsplit(" ", 1)[1])
    # usage ledger: the (tenant, class) cell splits by placement
    usage = usage_payload(eng)
    cell = usage["tenants"]["acme"]["classes"]["standard"]
    assert cell["by_placement"] == {"mega": 1, "packed": 1}
    assert usage["totals"]["by_placement"] == {"mega": 1, "packed": 1}
    assert "placement: 1 packed / 1 mega" in render_statusz(eng)


def test_gateway_serves_oversized_request_over_http(tmp_path):
    """Gateway e2e: an oversized NDJSON request POSTed to a running
    gateway streams back an ok record (placement mega) and its npz is
    byte-identical to the solo sharded drive."""
    from test_gateway import http, line, make_gateway

    gw, eng = make_gateway(tmp_path, buckets=(8,), keep_fields=True)
    try:
        st, recs, _ = http(gw, "POST", "/v1/solve",
                           line(id="giant", n=16, ntime=12,
                                dtype="float64"))
        assert st == 200
        (rec,) = recs
        assert rec["id"] == "giant" and rec["status"] == "ok", rec
        assert rec["placement"] == "mega"
    finally:
        gw.request_drain()
        assert gw.wait_drained(60)
        gw.close()
    solo = solo_sharded(HeatConfig(n=16, ntime=12, dtype="float64"))
    with np.load(tmp_path / "results" / "giant.npz") as z:
        assert z["T"].tobytes() == solo.tobytes()


# --- config / CLI surfaces ---------------------------------------------------


def test_parse_mega_lanes_grammar_and_validation():
    assert parse_mega_lanes("auto") is None
    assert parse_mega_lanes("0") == 0
    assert parse_mega_lanes(3) == 3
    with pytest.raises(ValueError, match="mega-lanes"):
        parse_mega_lanes("sideways")
    with pytest.raises(ValueError, match="mega-lanes"):
        parse_mega_lanes("-1")
    with pytest.raises(ValueError, match="mega_lanes"):
        ServeConfig(mega_lanes=-2)
    assert ServeConfig(mega_lanes=None).mega_lanes is None


def test_serve_cli_mega_flags(tmp_cwd, capsys):
    from heat_tpu.cli import main

    reqs = tmp_cwd / "reqs.jsonl"
    reqs.write_text('{"id": "big", "n": 16, "ntime": 8, '
                    '"dtype": "float64"}\n'
                    '{"id": "small", "n": 8, "ntime": 8, '
                    '"dtype": "float64"}\n')
    # mega off: the overflow is a rejection with the hint in its record
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "8",
               "--chunk", "8", "--mega-lanes", "0"])
    out = capsys.readouterr().out
    assert rc == 1
    records = {r["id"]: r for r in
               (json.loads(l) for l in out.splitlines()
                if l.startswith("{") and '"serve_request"' in l)}
    assert records["big"]["status"] == "rejected"
    assert records["big"]["hint"] == "enable --mega-lanes"
    assert records["small"]["status"] == "ok"
    # mega on (auto, 8-device harness): both serve; the report says so
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "8",
               "--chunk", "8"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "2 ok" in out
    assert "placement: 1 packed, 1 mega" in out
    # bad value is a CLI error
    rc = main(["serve", "--requests", "reqs.jsonl", "--buckets", "8",
               "--mega-lanes", "many"])
    assert rc == 2
    assert "mega-lanes" in capsys.readouterr().err


def test_info_prints_serve_placement_line(capsys):
    from heat_tpu.cli import main

    assert main(["info"]) == 0
    out = capsys.readouterr().out
    assert "serve placement: two-tier" in out
    assert "mega-lanes default 1" in out   # the 8-device harness


def test_serve_mega_lab_harness_smoke(tmp_path):
    """The mega lab harness runs end-to-end on a tiny population and
    emits every field the committed artifact relies on. The 10% perf
    ratio is deliberately NOT asserted at toy scale (the mega tier's
    fixed cost dominates a 0.1 s drain); the structural gates are."""
    import importlib.util
    import sys
    from pathlib import Path

    bench_dir = Path(__file__).resolve().parent.parent / "benchmarks"
    sys.path.insert(0, str(bench_dir))
    try:
        spec = importlib.util.spec_from_file_location(
            "serve_mega_lab_smoke", bench_dir / "serve_mega_lab.py")
        lab = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lab)
        out = tmp_path / "serve_mega_lab.json"
        lab.main(["--requests", "4", "--lanes", "2", "--chunk", "8",
                  "--waves", "1", "--oversized-side", "64",
                  "--oversized-ntimes", "8", "--out", str(out)])
    finally:
        sys.path.remove(str(bench_dir))
    rec = json.loads(out.read_text())
    assert rec["bench"] == "serve_mega_lab"
    assert rec["mega_bit_identical"] is True
    assert rec["packed_bit_identical"] is True
    assert rec["zero_overflow_rejections"] is True
    assert rec["mega_resident"]["mega_statuses"] == ["ok"]
    assert rec["mega_resident"]["mega_placements"] == ["mega"]
    assert rec["mega_resident"]["warm_mega_compiles"] == 0
    assert rec["packed_throughput_ratio"] is not None
    assert "packed_within_10pct" in rec
