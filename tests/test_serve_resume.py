"""Zero-downtime serving (ISSUE 17): engine-state checkpoint, crash-safe
``serve --resume``, and drain-as-handoff.

The load-bearing contracts:

- a kill at any checkpointed boundary is invisible in the results:
  resuming from the surviving generation yields npz BYTE-IDENTICAL to
  the uninterrupted run — packed and mega placements, dispatch depths
  0 and 2 (the consistent cut is the empty-pipeline boundary, and the
  lane reseed rides the same ``load_lane`` path ``maybe_grow``
  transplants already proved bit-exact);
- queued requests re-enter in original policy order (fifo golden trace
  + edf), and usage billing resumes from the stamped partials with no
  step double-billed;
- a corrupt manifest is quarantined loudly and discovery falls back one
  generation;
- ``until=steady`` lanes resume with their EWMA/prediction state
  re-seeded, retiring at the same boundary as the uninterrupted run;
- ``POST /drainz?handoff=1`` checkpoints at the next empty-pipeline cut
  without waiting for lanes to finish, and a second engine picks the
  work up over HTTP.
"""

import json
import os
import re
import signal
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from heat_tpu.config import HeatConfig
from heat_tpu.runtime import checkpoint as ckpt_mod
from heat_tpu.runtime import faults
from heat_tpu.serve import Engine, ServeConfig
from heat_tpu.serve.resume import resume_engine


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


def quiet(**kw) -> ServeConfig:
    kw.setdefault("emit_records", False)
    kw.setdefault("lanes", 2)
    kw.setdefault("chunk", 8)
    kw.setdefault("buckets", (32, 48))
    return ServeConfig(**kw)


# mixed sizes/steps: 6 requests over 2 lanes forces continuous-batching
# admissions, step counts not all chunk multiples exercise tails (kept
# small — this file rides the tier-1 wall budget)
WAVE = [
    HeatConfig(n=17, ntime=21, dtype="float64", bc="edges", ic="hat"),
    HeatConfig(n=32, ntime=34, dtype="float64", bc="ghost", ic="uniform"),
    HeatConfig(n=24, ntime=28, dtype="float64", bc="edges", ic="hat_small",
               nu=0.1),
    HeatConfig(n=40, ntime=12, dtype="float64", bc="edges", ic="hat"),
    HeatConfig(n=20, ntime=40, dtype="float64", bc="ghost", ic="hat",
               bc_value=2.5),
    HeatConfig(n=17, ntime=19, dtype="float64", bc="ghost", ic="hat_half"),
]


def run_wave(tmp_path, tag, cfgs, engine=None, ids=None, **kw):
    out = tmp_path / tag
    eng = engine or Engine(quiet(out_dir=str(out),
                                 engine_ckpt_dir=str(tmp_path
                                                     / f"{tag}-ckpt"),
                                 **kw))
    for i, cfg in enumerate(cfgs):
        eng.submit(cfg, request_id=(ids[i] if ids else f"r{i}"))
    return eng, {r["id"]: r for r in eng.results()}


def kill_after(ckdir: Path, gen: int, outdir: Path = None):
    """Simulate a SIGKILL right after generation ``gen`` became durable:
    delete every newer generation, and (when ``outdir`` is given) every
    result file the survivor does not list as done — exactly the on-disk
    state the FIFO writer ordering guarantees."""
    man = json.loads(
        (ckdir / ckpt_mod.ENGINE_MANIFEST_FMT.format(gen=gen)).read_text())
    for p in list(ckdir.glob("engine_gen*")):
        if int(re.search(r"gen(\d+)", p.name).group(1)) > gen:
            p.unlink()
    if outdir is not None and outdir.is_dir():
        done = set(man["done"])
        for p in list(outdir.glob("*.npz")):
            if p.stem not in done:
                p.unlink()
    return man


# --- kill-at-boundary -> resume byte-identity --------------------------------


@pytest.mark.parametrize("depth", [0, 2])
def test_resume_bit_identity_packed(tmp_path, depth):
    """Acceptance: kill at the first checkpointed boundary mid-wave,
    resume, and every npz — survivors and re-served alike — is
    byte-identical to the uninterrupted run, at depths 0 and 2."""
    _, golden = run_wave(tmp_path, "golden", WAVE, dispatch_depth=depth)
    run_wave(tmp_path, "killed", WAVE, dispatch_depth=depth,
             engine_ckpt_interval=3)
    ck = tmp_path / "killed-ckpt"
    man = kill_after(ck, 1, tmp_path / "killed")
    assert man["inflight"], "cut must land mid-wave to prove anything"

    eng = Engine(quiet(out_dir=str(tmp_path / "resumed"),
                       dispatch_depth=depth, engine_ckpt_dir=str(ck)))
    skip = resume_engine(eng, ck)
    assert skip == {f"r{i}" for i in range(len(WAVE))}
    resumed = {r["id"]: r for r in eng.results()}
    assert all(r["status"] == "ok" for r in resumed.values())
    assert all(r["resumed"] for r in resumed.values())

    for rid in golden:
        a = tmp_path / "golden" / f"{rid}.npz"
        b = tmp_path / "killed" / f"{rid}.npz"
        if not b.exists():
            b = tmp_path / "resumed" / f"{rid}.npz"
        assert a.read_bytes() == b.read_bytes(), rid


def test_resume_bit_identity_pallas_kernel(tmp_path):
    """The lane-kernel knob survives resume: a float32 wave under
    lane_kernel='pallas' (falls back to the bit-identical XLA oracle off
    TPU) resumes byte-identically."""
    wave = [c.with_(dtype="float32") for c in WAVE[:3]]
    _, golden = run_wave(tmp_path, "golden", wave, lane_kernel="pallas")
    run_wave(tmp_path, "killed", wave, lane_kernel="pallas",
             engine_ckpt_interval=2)
    ck = tmp_path / "killed-ckpt"
    kill_after(ck, 1, tmp_path / "killed")
    eng = Engine(quiet(out_dir=str(tmp_path / "resumed"),
                       lane_kernel="pallas", engine_ckpt_dir=str(ck)))
    resume_engine(eng, ck)
    resumed = {r["id"]: r for r in eng.results()}
    assert all(r["status"] == "ok" for r in resumed.values())
    for rid in golden:
        a = tmp_path / "golden" / f"{rid}.npz"
        b = tmp_path / "killed" / f"{rid}.npz"
        if not b.exists():
            b = tmp_path / "resumed" / f"{rid}.npz"
        assert a.read_bytes() == b.read_bytes(), rid


# n=16 overflows the (8,) bucket table and divides the auto 4x2 mesh of
# the 8-device harness (the test_serve_mega.py shape)
MEGA_CFG = HeatConfig(n=16, ntime=37, dtype="float64", bc="edges")


@pytest.mark.parametrize("depth", [0, 2])
def test_resume_bit_identity_mega(tmp_path, depth):
    """A mesh-spanning mega-lane killed mid-solve resumes bit-exactly:
    the owned-cell crop -> seed round trip at a chunk boundary is the
    same one the mega engine's rollback path already rides."""
    kw = dict(buckets=(8,), dispatch_depth=depth)
    _, golden = run_wave(tmp_path, "golden", [MEGA_CFG], ids=["big"], **kw)
    assert golden["big"]["placement"] == "mega"
    run_wave(tmp_path, "killed", [MEGA_CFG], ids=["big"],
             engine_ckpt_interval=2, **kw)
    ck = tmp_path / "killed-ckpt"
    man = kill_after(ck, 1, tmp_path / "killed")
    assert [e["placement"] for e in man["inflight"]] == ["mega"]
    assert 0 < man["inflight"][0]["remaining"] < MEGA_CFG.ntime

    eng = Engine(quiet(out_dir=str(tmp_path / "resumed"),
                       engine_ckpt_dir=str(ck), **kw))
    resume_engine(eng, ck)
    resumed = {r["id"]: r for r in eng.results()}
    assert resumed["big"]["status"] == "ok"
    assert ((tmp_path / "golden" / "big.npz").read_bytes()
            == (tmp_path / "resumed" / "big.npz").read_bytes())


# --- policy-order preservation across resume ---------------------------------


def _order_wave():
    """9 tiny same-bucket requests, deadlines deliberately anti-submit-
    order so edf and fifo produce DIFFERENT admission traces."""
    cfgs, deadlines = [], []
    for i in range(9):
        cfgs.append(HeatConfig(n=16, ntime=24 + 8 * (i % 3),
                               dtype="float64"))
        deadlines.append(1e6 - 1e4 * i)   # later submits = tighter
    return cfgs, deadlines


@pytest.mark.parametrize("policy", ["fifo", "edf"])
def test_resume_preserves_policy_order(tmp_path, policy):
    """Queued requests recovered from a manifest re-enter in the SAME
    relative order the uninterrupted engine would have admitted them:
    the manifest replays submits in original seq order and the policy
    queue re-sorts, so fifo keeps submit order and edf keeps deadline
    order — bit-for-bit against the golden admission trace."""
    cfgs, deadlines = _order_wave()

    def submit_all(eng):
        for i, cfg in enumerate(cfgs):
            eng.submit(cfg, request_id=f"r{i}", deadline_ms=deadlines[i])

    golden_eng = Engine(quiet(lanes=1, buckets=(16,), policy=policy))
    submit_all(golden_eng)
    golden_eng.results()
    golden_trace = golden_eng.admission_trace

    killed_eng = Engine(quiet(lanes=1, buckets=(16,), policy=policy,
                              engine_ckpt_interval=2,
                              engine_ckpt_dir=str(tmp_path / "ck")))
    submit_all(killed_eng)
    killed_eng.results()
    man = kill_after(tmp_path / "ck", 1)
    queued_ids = [e["id"] for e in man["queued"]]
    assert len(queued_ids) >= 3, "cut must leave a real queue"

    resumed_eng = Engine(quiet(lanes=1, buckets=(16,), policy=policy))
    resume_engine(resumed_eng, tmp_path / "ck")
    resumed_eng.results()
    resumed_order = [rid for rid in resumed_eng.admission_trace
                     if rid in set(queued_ids)]
    golden_order = [rid for rid in golden_trace
                    if rid in set(queued_ids)]
    assert resumed_order == golden_order


# --- usage-ledger reconciliation ---------------------------------------------


def test_resume_usage_partials_no_double_billing(tmp_path):
    """Billing spans incarnations exactly once: the resumed record's
    steps/chunks equal the uninterrupted run's (the countdown and the
    cumulative chunk counter restore from the manifest), its lane_s
    folds the checkpointed partial in, and the ledger still reconciles
    totals == sum of per-record stamps."""
    _, golden = run_wave(tmp_path, "golden", WAVE)
    run_wave(tmp_path, "killed", WAVE, engine_ckpt_interval=3)
    ck = tmp_path / "killed-ckpt"
    man = kill_after(ck, 1, tmp_path / "killed")
    partials = {e["id"]: e["lane_s"] for e in man["inflight"]}

    eng = Engine(quiet(out_dir=str(tmp_path / "resumed"), prof=True,
                       engine_ckpt_dir=str(ck)))
    resume_engine(eng, ck)
    resumed = {r["id"]: r for r in eng.results()}
    for rid, rec in resumed.items():
        g = golden[rid]
        assert rec["usage"]["steps"] == g["usage"]["steps"], rid
        assert rec["usage"]["chunks"] == g["usage"]["chunks"], rid
        assert rec["steps_done"] == g["steps_done"], rid
        if rid in partials:
            # the stamped partial is a floor on the resumed wall
            assert rec["usage"]["lane_s"] >= partials[rid] - 1e-9, rid
    # the engine summary carries the recovery count
    s = eng.summary()
    assert s["serve_resumed"] == len(resumed)
    # ledger reconciliation: totals == sum of the per-record stamps
    ledger = eng.prof.ledger.snapshot()
    assert ledger["totals"]["steps"] == sum(
        r["usage"]["steps"] for r in resumed.values())
    assert ledger["totals"]["requests"] == len(resumed)


# --- corrupt manifest: quarantine + fall back one generation -----------------


def test_corrupt_manifest_quarantines_and_falls_back(tmp_path, capsys):
    """A damaged newest manifest must not take resume down: discovery
    quarantines it to *.corrupt with a loud line and restores from the
    previous generation — which still yields byte-identical results
    (just with more steps to re-serve)."""
    _, golden = run_wave(tmp_path, "golden", WAVE)
    run_wave(tmp_path, "killed", WAVE, engine_ckpt_interval=2)
    ck = tmp_path / "killed-ckpt"
    kill_after(ck, 2, tmp_path / "killed")
    newest = ck / ckpt_mod.ENGINE_MANIFEST_FMT.format(gen=2)
    raw = bytearray(newest.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    newest.write_bytes(bytes(raw))
    # the fall-back loses gen-2's done-set authority, so re-serve from
    # the gen-1 view: wipe result files gen 1 does not list as done
    # (keeping the corrupted gen-2 manifest in place for discovery)
    man1 = json.loads(
        (ck / ckpt_mod.ENGINE_MANIFEST_FMT.format(gen=1)).read_text())
    for p in (tmp_path / "killed").glob("*.npz"):
        if p.stem not in set(man1["done"]):
            p.unlink()

    eng = Engine(quiet(out_dir=str(tmp_path / "resumed"),
                       engine_ckpt_dir=str(ck)))
    resume_engine(eng, ck)
    out = capsys.readouterr().out + capsys.readouterr().err
    assert "quarantin" in out
    assert "falling back one generation" in out
    assert (ck / (newest.name + ".corrupt")).exists()
    assert eng._engine_ckpt_gen == 1   # restored from the survivor

    resumed = {r["id"]: r for r in eng.results()}
    assert all(r["status"] == "ok" for r in resumed.values())
    for rid in golden:
        a = tmp_path / "golden" / f"{rid}.npz"
        b = tmp_path / "killed" / f"{rid}.npz"
        if not b.exists():
            b = tmp_path / "resumed" / f"{rid}.npz"
        assert a.read_bytes() == b.read_bytes(), rid


def test_ckpt_manifest_corrupt_fault_spec(tmp_path):
    """The ckpt-manifest-corrupt@N injection damages the Nth generation
    AT WRITE (the chaos lab's knob for the quarantine path): resume must
    land on the last clean generation."""
    run_wave(tmp_path, "killed", WAVE, engine_ckpt_interval=3,
             inject="ckpt-manifest-corrupt@2")
    ck = tmp_path / "killed-ckpt"
    gens = sorted(int(re.search(r"gen(\d+)", p.name).group(1))
                  for p in ck.glob("engine_gen*.json"))
    assert 2 in gens
    # make the damaged generation the newest, as a kill right after it
    # would have: discovery must trip on it, not sail past
    for p in list(ck.glob("engine_gen*")):
        if int(re.search(r"gen(\d+)", p.name).group(1)) > 2:
            p.unlink()
    eng = Engine(quiet(engine_ckpt_dir=str(ck)))
    resume_engine(eng, ck)
    assert eng._engine_ckpt_gen == 1
    assert (ck / (ckpt_mod.ENGINE_MANIFEST_FMT.format(gen=2)
                  + ".corrupt")).exists()


def test_fingerprint_mismatch_is_loud(tmp_path):
    """A manifest whose entry fingerprint does not match its own config
    must refuse to resume — never silently continue different physics."""
    run_wave(tmp_path, "killed", WAVE[:2], engine_ckpt_interval=1)
    ck = tmp_path / "killed-ckpt"
    man = kill_after(ck, 1)
    assert man["inflight"] or man["queued"]
    path = ck / ckpt_mod.ENGINE_MANIFEST_FMT.format(gen=1)
    man = json.loads(path.read_text())
    rows = man["inflight"] + man["queued"]
    rows[0]["cfg"]["nu"] = rows[0]["cfg"]["nu"] * 2   # different physics
    path.write_text(json.dumps(man, sort_keys=True))
    eng = Engine(quiet())
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        resume_engine(eng, ck)


def test_resume_manifest_with_retired_exchange_field(tmp_path):
    """A manifest written before ``HeatConfig.exchange`` was removed
    carries ``"exchange"`` in every entry's config. Resume drops that one
    retired key and finishes the wave byte-identically; any other key
    the config does not know still fails loudly."""
    wave = WAVE[:3]
    _, golden = run_wave(tmp_path, "golden", wave)
    run_wave(tmp_path, "killed", wave, engine_ckpt_interval=2)
    ck = tmp_path / "killed-ckpt"
    kill_after(ck, 1, tmp_path / "killed")
    path = ck / ckpt_mod.ENGINE_MANIFEST_FMT.format(gen=1)
    man = json.loads(path.read_text())
    rows = man["inflight"] + man["queued"]
    assert rows, "cut must land mid-wave to prove anything"
    for e in rows:
        assert "exchange" not in e["cfg"]
        e["cfg"]["exchange"] = "indep"      # the older writer's form
    path.write_text(json.dumps(man, sort_keys=True))

    eng = Engine(quiet(out_dir=str(tmp_path / "resumed"),
                       engine_ckpt_dir=str(ck)))
    assert resume_engine(eng, ck) == {f"r{i}" for i in range(len(wave))}
    resumed = {r["id"]: r for r in eng.results()}
    assert all(r["status"] == "ok" for r in resumed.values())
    for rid in golden:
        a = tmp_path / "golden" / f"{rid}.npz"
        b = tmp_path / "killed" / f"{rid}.npz"
        if not b.exists():
            b = tmp_path / "resumed" / f"{rid}.npz"
        assert a.read_bytes() == b.read_bytes(), rid

    from heat_tpu.serve.resume import config_from_manifest

    with pytest.raises(TypeError, match="no_such_field"):
        config_from_manifest({**rows[0]["cfg"], "no_such_field": 1})


def test_resume_empty_dir_is_loud_fresh_start(tmp_path, capsys):
    eng = Engine(quiet())
    assert resume_engine(eng, tmp_path / "nowhere") == set()
    assert "starting fresh" in capsys.readouterr().out


# --- until=steady lanes resume with prediction state -------------------------


def test_steady_lane_resumes_with_reseeded_ewma(tmp_path):
    """An until=steady lane killed mid-decay retires at the SAME
    boundary with the SAME bytes after resume: the residual EWMA and
    the rate-fuser observations ride the manifest (a cold restart of
    the observatory would re-warm the EWMA and exit late)."""
    cfg = HeatConfig(n=12, ntime=160, dtype="float64", bc="edges",
                     ic="sine")
    tol = 2e-3
    gkw = dict(lanes=1, buckets=(16,), chunk=8, out_dir=None,
               keep_fields=True)
    geng = Engine(quiet(**gkw))
    geng.submit(cfg, request_id="s", until="steady", tol=tol)
    golden = {r["id"]: r for r in geng.results()}["s"]
    assert golden["exit"] == "steady"
    assert golden["steps_done"] < cfg.ntime

    keng = Engine(quiet(engine_ckpt_interval=3,
                        engine_ckpt_dir=str(tmp_path / "ck"), **gkw))
    keng.submit(cfg, request_id="s", until="steady", tol=tol)
    keng.results()
    man = kill_after(tmp_path / "ck", 1)
    entry = man["inflight"][0]
    assert entry["numerics"] is not None
    assert entry["numerics"]["resid_ewma"] is not None
    assert 0 < entry["steps_done"] < golden["steps_done"]

    reng = Engine(quiet(**gkw))
    resume_engine(reng, tmp_path / "ck")
    rec = {r["id"]: r for r in reng.results()}["s"]
    assert rec["exit"] == "steady"
    assert rec["steps_done"] == golden["steps_done"]
    assert (np.asarray(rec["T"]).tobytes()
            == np.asarray(golden["T"]).tobytes())


# --- drain-as-handoff + gateway e2e ------------------------------------------


def test_handoff_drain_checkpoints_without_finishing(tmp_path):
    """begin_drain(handoff=True) checkpoints at the next empty-pipeline
    cut WITHOUT waiting for lanes to finish: occupants stay status
    'running' (no terminal records), and the manifest carries them —
    a second engine finishes the work byte-identically."""
    big = [c.with_(ntime=c.ntime * 4) for c in WAVE]   # long enough to
    _, golden_big = run_wave(tmp_path, "goldenb", big)  # catch mid-flight
    ck = tmp_path / "hand-ckpt"
    eng = Engine(quiet(out_dir=str(tmp_path / "hand"),
                       engine_ckpt_interval=1000,  # cadence off the path
                       engine_ckpt_dir=str(ck)))
    eng.start()
    for i, cfg in enumerate(big):
        eng.submit(cfg, request_id=f"r{i}")
    # handoff immediately: lanes are mid-solve (or still queued)
    eng.begin_drain(handoff=True)
    assert eng.shutdown(timeout=120)
    mans = sorted(ck.glob("engine_gen*.json"))
    assert len(mans) == 1
    man = json.loads(mans[0].read_text())
    assert man["reason"] == "handoff"
    # whatever was occupying a lane at the cut is still 'running'
    for e in man["inflight"]:
        rec = eng.poll(e["id"])
        if e["remaining"] < big[int(e["id"][1:])].ntime:
            assert rec["status"] == "running"

    eng2 = Engine(quiet(out_dir=str(tmp_path / "hand2"),
                        engine_ckpt_dir=str(ck)))
    resume_engine(eng2, ck)
    resumed = {r["id"]: r for r in eng2.results()}
    assert all(r["status"] == "ok" for r in resumed.values())
    for i in range(len(big)):
        a = tmp_path / "goldenb" / f"r{i}.npz"
        b = tmp_path / "hand" / f"r{i}.npz"
        if not b.exists():
            b = tmp_path / "hand2" / f"r{i}.npz"
        assert a.read_bytes() == b.read_bytes(), f"r{i}"


def test_gateway_handoff_resume_e2e(tmp_path):
    """The whole zero-downtime story over HTTP: submit through gateway
    A, POST /drainz?handoff=1, bring gateway B up with --resume
    semantics, and collect byte-identical results — plus the resume
    surface on /metrics and /statusz."""
    import urllib.request

    from heat_tpu.serve.gateway import Gateway, render_metrics, \
        render_statusz

    def http(gw, method, path, body=None):
        req = urllib.request.Request(
            f"http://{gw.address}{path}",
            data=body.encode() if body is not None else None,
            method=method)
        resp = urllib.request.urlopen(req, timeout=60)
        return resp.status, [json.loads(l) for l in
                             resp.read().decode().splitlines() if l.strip()]

    _, golden = run_wave(tmp_path, "golden",
                         [c.with_(ntime=c.ntime * 4) for c in WAVE])
    ck = tmp_path / "gw-ckpt"
    engA = Engine(quiet(out_dir=str(tmp_path / "gwA"),
                        engine_ckpt_interval=1000,
                        engine_ckpt_dir=str(ck)))
    gwA = Gateway(engA, "127.0.0.1", 0).start()
    try:
        for i, c in enumerate(WAVE):
            st, _ = http(gwA, "POST", "/v1/solve?wait=0",
                         json.dumps({"id": f"r{i}", "n": c.n,
                                     "ntime": c.ntime * 4,
                                     "dtype": c.dtype, "bc": c.bc,
                                     "bc_value": c.bc_value, "nu": c.nu,
                                     "ic": c.ic}) + "\n")
            assert st == 202   # accepted, not waited on
        st, (d,) = http(gwA, "POST", "/drainz?handoff=1")
        assert st == 200 and d["handoff"] is True
        assert gwA.wait_drained(120)
    finally:
        engA.begin_drain(handoff=True)
        engA.shutdown(timeout=120)
        gwA.close()
    man = json.loads(sorted(ck.glob("engine_gen*.json"))[-1].read_text())
    assert man["reason"] == "handoff"

    engB = Engine(quiet(out_dir=str(tmp_path / "gwB"),
                        engine_ckpt_dir=str(ck)))
    resume_engine(engB, ck)
    gwB = Gateway(engB, "127.0.0.1", 0).start()
    try:
        for i in range(len(WAVE)):
            st, (rec,) = http(gwB, "GET", f"/v1/requests/r{i}")
            assert st == 200
        m = render_metrics(engB)
        assert re.search(r"heat_tpu_serve_resumed_requests_total \d", m)
        assert "heat_tpu_engine_ckpt_generation" in m
        assert "re-admitted from a checkpoint" in render_statusz(engB)
        gwB.request_drain()
        assert gwB.wait_drained(120)
    finally:
        engB.shutdown(timeout=120)
        gwB.close()
    for i in range(len(WAVE)):
        a = tmp_path / "golden" / f"r{i}.npz"
        b = tmp_path / "gwA" / f"r{i}.npz"
        done_at_cut = f"r{i}" in set(man["done"])
        if not done_at_cut:
            b = tmp_path / "gwB" / f"r{i}.npz"
        assert a.read_bytes() == b.read_bytes(), f"r{i}"


# --- engine-kill fault + CLI chaos e2e ---------------------------------------


def test_engine_kill_spec_parses_and_requires_step():
    fs = faults.parse_spec("engine-kill@7")
    assert fs and fs[0].kind == "engine-kill" and fs[0].step == 7
    with pytest.raises(ValueError):
        faults.parse_spec("engine-kill")   # step is the whole point
    # valid with and without a step
    faults.parse_spec("ckpt-manifest-corrupt@2")
    faults.parse_spec("ckpt-manifest-corrupt")


def test_damage_manifest_flips_bytes_once(tmp_path):
    p = tmp_path / "m.json"
    p.write_text(json.dumps({"k": "v" * 64}))
    before = p.read_bytes()
    plan = faults.FaultPlan("ckpt-manifest-corrupt@3")
    plan.damage_manifest(p, 2)          # below the step: untouched
    assert p.read_bytes() == before
    plan.damage_manifest(p, 3)          # at the step: damaged
    assert p.read_bytes() != before
    damaged = p.read_bytes()
    plan.damage_manifest(p, 4)          # fire-once
    assert p.read_bytes() == damaged


@pytest.mark.slow
def test_cli_engine_kill_then_resume_byte_identical(tmp_path):
    """The chaos e2e, through the real CLI: serve with engine-kill@N
    dies by SIGKILL mid-wave; serve --resume finishes the wave; every
    npz matches a clean run byte for byte."""
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text("".join(
        json.dumps({"id": f"r{i}", "n": c.n, "ntime": c.ntime,
                    "dtype": c.dtype, "bc": c.bc, "bc_value": c.bc_value,
                    "nu": c.nu, "ic": c.ic}) + "\n"
        for i, c in enumerate(WAVE)))
    base = [sys.executable, "-m", "heat_tpu", "serve",
            "--requests", str(reqs), "--lanes", "2", "--chunk", "8",
            "--buckets", "32,48"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_ENABLE_X64": "1",
           "PYTHONPATH": (str(Path(__file__).resolve().parent.parent)
                          + os.pathsep + os.environ.get("PYTHONPATH", ""))}

    rc = subprocess.run(base + ["--out-dir", str(tmp_path / "golden")],
                        env=env, capture_output=True, timeout=300)
    assert rc.returncode == 0, rc.stderr.decode()[-2000:]

    ck = tmp_path / "ck"
    rc = subprocess.run(
        base + ["--out-dir", str(tmp_path / "killed"),
                "--engine-ckpt-interval", "2",
                "--engine-ckpt-dir", str(ck),
                "--inject", "engine-kill@12"],
        env=env, capture_output=True, timeout=300)
    assert rc.returncode == -signal.SIGKILL, (rc.returncode,
                                              rc.stderr.decode()[-2000:])
    mans = sorted(ck.glob("engine_gen*.json"))
    assert mans, "at least one generation must be durable before the kill"
    man = json.loads(mans[-1].read_text())
    # scrub result files the surviving manifest does not vouch for (a
    # kill can leave a half-published npz newer than the manifest; the
    # resume contract only trusts the manifest's done set)
    done = set(man["done"])
    for p in (tmp_path / "killed").glob("*.npz"):
        if p.stem not in done:
            p.unlink()

    rc = subprocess.run(
        base + ["--out-dir", str(tmp_path / "resumed"),
                "--engine-ckpt-interval", "2",
                "--engine-ckpt-dir", str(ck), "--resume", str(ck)],
        env=env, capture_output=True, timeout=300)
    assert rc.returncode == 0, rc.stderr.decode()[-2000:]
    out = rc.stdout.decode()
    assert "serve_resumed" in out

    for i in range(len(WAVE)):
        a = tmp_path / "golden" / f"r{i}.npz"
        b = tmp_path / "killed" / f"r{i}.npz"
        if not b.exists():
            b = tmp_path / "resumed" / f"r{i}.npz"
        assert a.read_bytes() == b.read_bytes(), f"r{i}"


# --- resume-aware front doors ------------------------------------------------


def test_serve_requests_skip_ids(tmp_path):
    """File rows the manifest accounts for are not re-submitted — the
    resume replay is the authority on their state."""
    from heat_tpu.serve import serve_requests

    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text(json.dumps({"id": "a", "n": 16, "ntime": 8}) + "\n"
                    + json.dumps({"id": "b", "n": 16, "ntime": 8}) + "\n")
    records, summary = serve_requests(
        reqs, quiet(lanes=1, buckets=(16,)), skip_ids={"a"})
    assert [r["id"] for r in records] == ["b"]
    assert summary["requests"] == 1


def test_engine_ckpt_interval_validates():
    with pytest.raises(ValueError, match="engine_ckpt_interval"):
        ServeConfig(engine_ckpt_interval=-1)


def test_flight_dump_skipped_without_dirs(tmp_cwd):
    """No flight_dir and no out_dir -> the dump is SKIPPED, never
    written to cwd (the repo root grew 81 stray dumps this way)."""
    eng = Engine(quiet(trace_buffer=64))
    eng._flight_dump("unit-test trigger")
    assert not list(tmp_cwd.glob("flightrec-*.trace.json"))
    assert eng.tracer.dumps == 0
