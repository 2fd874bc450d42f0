"""Request-scoped tracing + flight recorder (runtime/trace.py, ISSUE 7).

The load-bearing contracts:

- **Schema**: a ``--trace`` export is Chrome trace-event JSON Perfetto
  can load — required keys on every event, id-paired flow and async
  events, monotone timestamps per track, and the span taxonomy the
  README documents (lane occupancy, chunk-in-flight, boundary-fetch,
  queue-wait, writeback) actually present for a real drain.
- **Flight recorder**: an injected ``fetch-hang`` leaves an atomic
  ``flightrec-*.trace.json`` dump containing the wedged request's full
  span chain — without hanging the engine.
- **Bit-identity**: tracing on/off produces identical npz outputs at
  dispatch depths 0 and 2 (observability must never perturb physics).
"""

import json

import numpy as np
import pytest

from heat_tpu.config import HeatConfig
from heat_tpu.runtime import faults
from heat_tpu.runtime import trace as trace_mod
from heat_tpu.serve import Engine, ServeConfig


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.reset()
    yield
    faults.reset()


def quiet(**kw) -> ServeConfig:
    kw.setdefault("emit_records", False)
    return ServeConfig(**kw)


WAVE = [HeatConfig(n=16, ntime=24, dtype="float64"),
        HeatConfig(n=16, ntime=40, dtype="float64", nu=0.1),
        HeatConfig(n=24, ntime=32, dtype="float64", bc="ghost",
                   ic="uniform"),
        HeatConfig(n=16, ntime=16, dtype="float64", ic="hat_small")]


def drain(tmp_path, tag, **scfg_kw):
    out = tmp_path / tag
    eng = Engine(quiet(lanes=2, chunk=8, buckets=(24,), out_dir=str(out),
                       keep_fields=True, **scfg_kw))
    ids = [eng.submit(cfg, request_id=f"{tag}-{i}",
                      tenant=("acme", "free")[i % 2])
           for i, cfg in enumerate(WAVE)]
    recs = {r["id"]: r for r in eng.results()}
    return eng, recs, ids


# --- tracer unit contracts ----------------------------------------------------


def test_ring_is_bounded_and_disabled_tracer_records_nothing():
    t = trace_mod.Tracer(capacity=4)
    tr = t.track("p", "t")
    for i in range(32):
        t.instant(f"e{i}", tr)
    assert len(t) == 4 and t.dropped_hint
    # newest events survive, oldest dropped — ring, not truncation
    names = {e["name"] for e in t.to_chrome()["traceEvents"]
             if e["ph"] == "i"}
    assert names == {"e28", "e29", "e30", "e31"}

    off = trace_mod.Tracer(capacity=0)
    assert not off.enabled
    off.instant("x", off.track("p", "t"))
    off.complete("y", off.track("p", "t"), 0.0, 1.0)
    assert len(off) == 0
    # ids still mint (the record schema never depends on tracing state)
    assert off.mint_trace_id() != off.mint_trace_id()


def test_resolve_trace_env_and_flags(monkeypatch):
    monkeypatch.delenv(trace_mod.ENV_VAR, raising=False)
    assert trace_mod.resolve_trace(None, None) == (
        None, trace_mod.DEFAULT_BUFFER)
    assert trace_mod.resolve_trace("t.json", 512) == ("t.json", 512)
    monkeypatch.setenv(trace_mod.ENV_VAR, "env.json")
    assert trace_mod.resolve_trace(None, None) == (
        "env.json", trace_mod.DEFAULT_BUFFER)
    # the flag wins over the env path
    assert trace_mod.resolve_trace("flag.json", None)[0] == "flag.json"
    monkeypatch.setenv(trace_mod.ENV_VAR, "off")
    assert trace_mod.resolve_trace(None, None) == (None, 0)
    with pytest.raises(ValueError, match="trace-buffer"):
        trace_mod.resolve_trace("t.json", 0)
    with pytest.raises(ValueError, match="trace-buffer"):
        trace_mod.resolve_trace(None, -1)


def test_serve_config_validates_trace_knobs():
    with pytest.raises(ValueError, match="trace_buffer"):
        ServeConfig(trace_buffer=-1)
    with pytest.raises(ValueError, match="trace"):
        ServeConfig(trace="t.json", trace_buffer=0)


def test_counter_samples_render_in_export_and_summary():
    """The numerics observatory's 'C' counter samples (ISSUE 15): args
    flow to the Chrome export as counter tracks, and summarize() renders
    min/max/last per series. A disabled tracer records nothing."""
    t = trace_mod.Tracer(capacity=16)
    tr = t.track("lanes", "g0")
    t.counter("numerics lane 0", tr, {"resid": 1.0, "heat": 5.0})
    t.counter("numerics lane 0", tr, {"resid": 0.25, "heat": 4.0})
    chrome = t.to_chrome()
    cs = [e for e in chrome["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 2
    assert all(e["name"] == "numerics lane 0" for e in cs)
    assert cs[0]["args"] == {"resid": 1.0, "heat": 5.0}
    text = "\n".join(trace_mod.summarize(chrome))
    assert "counter tracks:" in text
    assert ("numerics lane 0/resid: 2 sample(s), min 0.25, max 1, "
            "last 0.25") in text
    assert "numerics lane 0/heat: 2 sample(s)" in text

    off = trace_mod.Tracer(capacity=0)
    off.counter("x", off.track("p", "t"), {"v": 1.0})
    assert len(off) == 0


def test_serve_trace_carries_numerics_counter_tracks(tmp_path):
    """A real drain with the observatory on exports per-lane residual/
    heat counter samples on the group's track."""
    path = tmp_path / "num.trace.json"
    drain(tmp_path, "ctr", trace=str(path))
    evs = json.loads(path.read_text())["traceEvents"]
    cs = [e for e in evs if e["ph"] == "C"]
    assert cs and any(e["name"].startswith("numerics lane") for e in cs)
    series = set()
    for e in cs:
        series |= set(e["args"])
    assert {"resid", "heat"} <= series
    text = "\n".join(trace_mod.summarize_file(path))
    assert "counter tracks:" in text and "numerics lane" in text


def test_trace_cli_triage_names_numerics_violation_dump(tmp_cwd, capsys):
    """`heat-tpu trace <flightrec-*.json>` prints a one-line triage verdict
    naming the likely trigger — a numerics violation here."""
    from heat_tpu.cli import main

    eng = Engine(quiet(lanes=1, chunk=4, buckets=(12,),
                       inject="perturb@6:eps=100",
                       flight_dir=str(tmp_cwd)))
    rid = eng.submit(HeatConfig(n=12, ntime=24, dtype="float32"))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[rid]["status"] == "ok"        # guard=warn observes only
    (dump,) = sorted(tmp_cwd.glob("flightrec-*.trace.json"))
    capsys.readouterr()
    assert main(["trace", dump.name]) == 0
    out = capsys.readouterr().out
    assert "numerics-violation" in out
    assert "flight-dump triage" in out and "likely trigger" in out


# --- export schema (the Perfetto-loadability contract) ------------------------


def test_trace_export_schema_and_span_taxonomy(tmp_path):
    """Acceptance: a full drain with --trace produces a loadable Chrome
    trace: required keys everywhere, paired flow/async ids, monotone ts
    per track, and one end-to-end request visible across queue -> lane ->
    writer tracks."""
    path = tmp_path / "serve.trace.json"
    _, recs, ids = drain(tmp_path, "schema", trace=str(path))
    assert all(recs[i]["status"] == "ok" for i in ids)

    obj = json.loads(path.read_text())
    evs = obj["traceEvents"]
    assert isinstance(evs, list) and len(evs) > 20

    for e in evs:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(e), e
        if e["ph"] == "X":
            assert e["dur"] >= 0

    # monotone ts per (pid, tid) track, in file order
    last = {}
    for e in evs:
        if e["ph"] == "M":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last.get(key, -1.0), (key, e)
        last[key] = e["ts"]

    # flow-event pairing: every started flow ends, steps belong to starts
    by_phase = {"s": set(), "t": set(), "f": set()}
    for e in evs:
        if e["ph"] in by_phase:
            assert e.get("id"), e
            by_phase[e["ph"]].add(e["id"])
    assert by_phase["s"] == by_phase["f"] and len(by_phase["s"]) == len(ids)
    assert by_phase["t"] <= by_phase["s"]

    # async queue-wait pairing (b/e share an id)
    b = {e["id"] for e in evs if e["ph"] == "b"}
    ee = {e["id"] for e in evs if e["ph"] == "e"}
    assert b == ee and len(b) == len(ids)

    # span taxonomy: the tracks and spans the README documents
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"heat.sched.boundary_fetch", "heat.sched.dispatch",
            "heat.lane.load", "heat.lane.writeback", "engine.run"} <= names
    assert any(n.startswith("chunk ") for n in names)
    written = {e["args"]["id"] for e in evs
               if e["ph"] == "X" and e["name"] == "heat.lane.writeback"}
    assert written == set(ids)   # one writeback span per request
    for rid in ids:
        assert rid in names      # one occupancy span per request
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert any(p.startswith("lanes ") for p in procs)
    assert {"queue", "writer"} <= procs

    # trace ids: minted per request, echoed on the record AND in events
    rec_tids = {recs[i]["trace_id"] for i in ids}
    assert len(rec_tids) == len(ids)
    ev_tids = {e["args"]["trace_id"] for e in evs
               if e.get("args", {}).get("trace_id")}
    assert rec_tids <= ev_tids


def test_trace_summary_renders_utilization_and_queue_waits(tmp_path):
    path = tmp_path / "s.trace.json"
    drain(tmp_path, "sum", trace=str(path))
    lines = trace_mod.summarize_file(path)
    text = "\n".join(lines)
    assert "lane utilization" in text and "lane 0" in text
    assert "top queue waits" in text and "tenant acme" in text
    assert "boundary-fetch wall" in text


def test_trace_cli_subcommand_and_serve_trace_flag(tmp_cwd, capsys):
    """`heat-tpu serve --trace` writes the export; `heat-tpu trace FILE`
    summarizes it (and rejects a non-trace file loudly)."""
    from heat_tpu.cli import main

    (tmp_cwd / "reqs.jsonl").write_text(
        '{"id": "a", "n": 16, "ntime": 16, "dtype": "float64"}\n')
    assert main(["serve", "--requests", "reqs.jsonl", "--buckets", "16",
                 "--chunk", "8", "--trace", "t.trace.json"]) == 0
    out = capsys.readouterr().out
    assert "wrote trace t.trace.json" in out
    assert main(["trace", "t.trace.json"]) == 0
    out = capsys.readouterr().out
    assert "lane utilization" in out and "top queue waits" in out

    (tmp_cwd / "bogus.json").write_text("[1, 2, 3]")
    assert main(["trace", "bogus.json"]) == 2
    assert main(["trace", "missing.json"]) == 2


# --- flight recorder ----------------------------------------------------------


def test_flight_dump_on_fetch_hang_contains_span_chain(tmp_path):
    """Acceptance: an injected fetch-hang run leaves a flight-recorder
    dump containing the wedged request's full span chain (submit flow ->
    queue-wait -> occupancy -> watchdog) without hanging the engine."""
    eng = Engine(quiet(lanes=2, chunk=8, buckets=(16,),
                       inject="fetch-hang:ms=1500", fetch_timeout_s=0.2,
                       flight_dir=str(tmp_path)))
    rid = eng.submit(HeatConfig(n=16, ntime=24, dtype="float64"))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[rid]["status"] == "error"
    assert eng.watchdog_fired == 1

    dumps = sorted(tmp_path.glob("flightrec-*.trace.json"))
    assert len(dumps) == 1
    evs = json.loads(dumps[0].read_text())["traceEvents"]
    tid = recs[rid]["trace_id"]
    phases = {e["ph"] for e in evs
              if e.get("id") == tid
              or e.get("args", {}).get("trace_id") == tid}
    assert "s" in phases                  # submit flow anchor
    assert {"b", "e"} <= phases           # queue-wait span
    assert "X" in phases                  # lane occupancy span
    occ = [e for e in evs if e["ph"] == "X" and e["name"] == rid]
    assert occ and occ[0]["args"]["status"] == "error"
    assert any(e["name"] == "watchdog-fired" for e in evs)
    # no torn dump left behind
    assert not list(tmp_path.glob("*.tmp"))


def test_flight_dump_on_quarantine_after_rollback_budget(tmp_path):
    """A deterministic blow-up that exhausts its rollback budget is the
    other postmortem trigger: the dump holds the rollback/quarantine
    instants for the doomed request."""
    eng = Engine(quiet(lanes=2, chunk=8, buckets=(16,), on_nan="rollback",
                       flight_dir=str(tmp_path)))
    boom = eng.submit(HeatConfig(n=16, ntime=200, dtype="float32",
                                 sigma=9.0))
    recs = {r["id"]: r for r in eng.results()}
    assert recs[boom]["status"] == "nonfinite"
    assert "deterministic blow-up" in recs[boom]["error"]
    # two dumps: the numerics observatory flags the envelope escape while
    # the field is still finite (ISSUE 15's early warning), THEN the
    # nonfinite path exhausts its rollback budget
    dumps = sorted(tmp_path.glob("flightrec-*.trace.json"))
    assert len(dumps) == 2
    first = [e["name"] for e in
             json.loads(dumps[0].read_text())["traceEvents"]
             if e["ph"] == "i"]
    assert "numerics-violation" in first
    evs = json.loads(dumps[1].read_text())["traceEvents"]
    names = [e["name"] for e in evs if e["ph"] == "i"]
    assert names.count("rollback") == 2 and "quarantine" in names


def test_no_dump_and_no_events_with_tracing_disabled(tmp_path):
    eng, recs, ids = drain(tmp_path, "off", trace_buffer=0,
                           inject="fetch-hang:ms=1500",
                           fetch_timeout_s=0.2,
                           flight_dir=str(tmp_path))
    assert not list(tmp_path.glob("flightrec-*"))
    assert len(eng.tracer) == 0
    # trace ids still minted: the record schema is tracing-independent
    assert all(recs[i]["trace_id"] for i in ids)


# --- overhead-lab harness -----------------------------------------------------


def test_trace_overhead_lab_harness_smoke(tmp_path):
    """The trace_overhead_lab harness runs end-to-end on a tiny workload
    and emits every field the committed artifact relies on. The 2% gate
    is deliberately NOT asserted here — 6 requests on a loaded CI box
    prove plumbing, not perf (the lab itself gates the real artifact)."""
    import importlib.util
    import sys
    from pathlib import Path

    bench_dir = Path(__file__).resolve().parent.parent / "benchmarks"
    sys.path.insert(0, str(bench_dir))
    try:
        spec = importlib.util.spec_from_file_location(
            "trace_overhead_lab_smoke", bench_dir / "trace_overhead_lab.py")
        lab = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lab)
        out = tmp_path / "trace_overhead_lab.json"
        lab.main(["--requests", "6", "--lanes", "2", "--chunk", "8",
                  "--repeats", "1", "--out", str(out)])
    finally:
        sys.path.remove(str(bench_dir))
    rec = json.loads(out.read_text())
    assert rec["bench"] == "trace_overhead_lab"
    for mode in ("off", "flightrec", "full"):
        assert rec[mode]["ok"] == 6
        assert rec[mode]["wall_s"] > 0
    assert rec["off"]["events"] == 0          # tracing truly off
    assert rec["full"]["events"] > 0
    assert rec["trace_export_nonempty"] is True
    assert "full_overhead_frac" in rec and "full_within_2pct_of_off" in rec


# --- bit-identity (observability must not perturb physics) --------------------


@pytest.mark.parametrize("depth", [0, 2])
def test_trace_on_off_bit_identical_npz(tmp_path, depth):
    path = tmp_path / f"d{depth}.trace.json"
    _, off_recs, ids_off = drain(tmp_path, f"off{depth}", trace_buffer=0,
                                 dispatch_depth=depth)
    _, on_recs, ids_on = drain(tmp_path, f"on{depth}", trace=str(path),
                               dispatch_depth=depth)
    for i_off, i_on in zip(ids_off, ids_on):
        assert off_recs[i_off]["status"] == on_recs[i_on]["status"] == "ok"
        np.testing.assert_array_equal(off_recs[i_off]["T"],
                                      on_recs[i_on]["T"])
        # and through the published npz files, byte-for-byte fields
        with np.load(tmp_path / f"off{depth}" / f"{i_off}.npz") as a, \
                np.load(tmp_path / f"on{depth}" / f"{i_on}.npz") as b:
            np.testing.assert_array_equal(a["T"], b["T"])
    assert path.exists()
