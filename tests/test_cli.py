"""CLI driver: the program-heat contract end to end (discovery, dumps,
stdout lines — fortran/serial/heat.f90:11-13,50-55,73-83)."""

import numpy as np
import pytest

from heat_tpu.cli import main
from heat_tpu.io import read_dat


@pytest.fixture
def input_dat(tmp_cwd):
    (tmp_cwd / "input.dat").write_text("32 0.25 0.05 2.0 5 1\n")
    return tmp_cwd


def test_run_writes_soln_and_prints_contract_lines(input_dat, capsys):
    rc = main(["run", "--backend", "xla", "--dtype", "float64", "--write-int"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "simulation completed!!!!" in out      # serial/heat.f90:73
    assert "total time:" in out                   # serial/heat.f90:74
    assert "Average time per timestep:" in out    # hip/heat.F90:323
    assert (input_dat / "int.dat").exists()
    axes, T = read_dat(input_dat / "soln.dat")
    assert T.shape == (32, 32)
    # int.dat holds the IC; soln.dat the diffused field (the hat edge
    # smears immediately; sum is conserved until the front hits the walls)
    _, T0 = read_dat(input_dat / "int.dat")
    assert T0.max() == 2.0
    assert not np.array_equal(T, T0)
    assert np.abs(T - T0).max() > 1e-3


def test_run_missing_input(tmp_cwd, capsys):
    rc = main(["run"])
    assert rc == 2
    assert "not found" in capsys.readouterr().err


def test_run_variant_preset(input_dat, capsys):
    rc = main(["run", "--variant", "cuda_cuf", "--json"])
    assert rc == 0
    out = capsys.readouterr().out
    assert '"backend": "xla"' in out


def test_run_ntime_overrides_the_input_file(input_dat, capsys):
    """The reference's flagship is cut in steps only: ``--ntime`` runs
    input.dat's grid for another step count."""
    rc = main(["run", "--backend", "sharded", "--mesh", "2x2", "--dtype",
               "float32", "--bc", "ghost", "--fuse-steps", "4", "--ntime",
               "7", "--json"])
    assert rc == 0
    assert '"ntime": 7' in capsys.readouterr().out


def test_run_sharded_with_mesh(input_dat):
    rc = main(["run", "--backend", "sharded", "--dtype", "float64",
               "--mesh", "4x2", "--report-sum"])
    assert rc == 0
    assert (input_dat / "soln.dat").exists()
    # per-shard files (reference per-rank contract) alongside the merged one
    shard_files = sorted(input_dat.glob("soln0*.dat"))
    assert len(shard_files) == 8
    merged = read_dat(input_dat / "soln.dat")[1]
    _, blk0 = read_dat(shard_files[0])
    np.testing.assert_array_equal(blk0, merged[:8, :16])


def test_heartbeat_lines_identical_across_backends(input_dat, capsys):
    main(["run", "--backend", "serial", "--dtype", "float64",
          "--heartbeat-every", "2"])
    serial_lines = [l for l in capsys.readouterr().out.splitlines()
                    if "time_it" in l]
    main(["run", "--backend", "xla", "--dtype", "float64",
          "--heartbeat-every", "2"])
    xla_lines = [l for l in capsys.readouterr().out.splitlines()
                 if "time_it" in l]
    assert serial_lines == xla_lines and len(serial_lines) == 2


def test_soln_flag_gating(tmp_cwd):
    # soln=0 in input.dat -> no dump (mpi+cuda/heat.F90:277: gated write)
    (tmp_cwd / "input.dat").write_text("16 0.25 0.05 2.0 2 0\n")
    rc = main(["run", "--backend", "serial", "--dtype", "float64"])
    assert rc == 0
    assert not (tmp_cwd / "soln.dat").exists()


def test_viz(input_dat):
    pytest.importorskip("matplotlib")
    main(["run", "--backend", "serial", "--dtype", "float64", "--write-int"])
    rc = main(["viz", "soln.dat", "--save", "sol.png"])
    assert rc == 0
    assert (input_dat / "sol.png").stat().st_size > 0
    # the reference's init.py workflow: render the pre-solve dump too
    assert main(["viz", "int.dat", "--save", "init.png"]) == 0
    assert (input_dat / "init.png").stat().st_size > 0


def test_info(capsys):
    assert main(["info"]) == 0
    out = capsys.readouterr().out
    assert "devices:" in out
    # PR-2's gloo unbreak and the persistent compile cache must be visible
    # to users, not only discoverable through a failed launch
    assert "gloo CPU collectives:" in out
    assert "compile cache:" in out
    assert ("warm" in out) or ("cold/empty" in out)
    # the trace defaults line (ISSUE 7): flight recorder + export knobs
    assert "trace defaults: flight recorder on" in out
    assert "--trace FILE" in out and "HEAT_TPU_TRACE" in out
    # the numerics observatory + prober lines (ISSUE 15): solution-quality
    # telemetry defaults and the canary knobs must be discoverable here
    assert "numerics observatory: on by default" in out
    assert "steady-tol" in out and "guard warn" in out
    assert "prober: off by default (--probe-interval" in out
    assert "sine-eigenmode" in out


def test_serve_cli_numerics_flags(tmp_cwd, capsys):
    """--numerics gates the observatory (and its summary line);
    --probe-interval validates against missing --listen at parse time."""
    (tmp_cwd / "reqs.jsonl").write_text(
        '{"id": "a", "n": 12, "ntime": 16, "dtype": "float32",'
        ' "ic": "uniform"}\n')
    base = ["serve", "--requests", "reqs.jsonl", "--buckets", "12",
            "--chunk", "4"]
    assert main(base) == 0
    out = capsys.readouterr().out
    # uniform under frozen edges converges instantly: one steady lane
    assert "numerics: 1 steady lane(s), 0 violation(s) (guard warn)" in out
    assert '"event": "steady_state"' in out

    assert main(base + ["--numerics", "off"]) == 0
    out = capsys.readouterr().out
    assert "steady lane(s)" not in out and "steady_state" not in out

    with pytest.raises(SystemExit):   # argparse rejects a bad guard
        main(base + ["--numerics-guard", "page-someone"])
    assert main(base + ["--probe-interval", "5"]) == 2
    assert "--probe-interval needs --listen" in capsys.readouterr().err
    assert main(base + ["--probe-interval", "-1", "--listen",
                        "127.0.0.1:0"]) == 2


def test_bad_mesh_arg():
    with pytest.raises(SystemExit):
        main(["run", "--mesh", "fourbytwo"])


def test_cli_plan_subcommand(tmp_cwd, capsys):
    """`heat-tpu plan` explains the execution plan without touching devices:
    kernel choice + geometry, mesh/halo economics, run-path validation."""
    from heat_tpu.cli import main

    (tmp_cwd / "input.dat").write_text("4096 0.25 0.05 2.0 100 0\n")
    assert main(["plan", "--backend", "pallas", "--dtype", "float32"]) == 0
    out = capsys.readouterr().out
    assert "thin-band 2D" in out and "per-pass chunk 16" in out

    assert main(["plan", "--backend", "sharded", "--dtype", "float32",
                 "--mesh", "4x4"]) == 0
    out = capsys.readouterr().out
    assert "local block 1024x1024" in out and "halo: width 23" in out  # k* = round(sqrt(1024/2))

    # f64 -> XLA fallback is reported honestly
    assert main(["plan", "--variant", "cuda_kernel"]) == 0
    assert "XLA fused stencil" in capsys.readouterr().out

    # run-path validation applies: bad mesh rank / divisibility
    assert main(["plan", "--backend", "sharded", "--ndim", "3",
                 "--mesh", "4x2"]) == 2
    assert main(["plan", "--backend", "sharded", "--mesh", "3x3"]) == 2


def test_cli_bench_subcommand_refuses_off_tpu(capsys):
    """`heat-tpu bench` names its metric per chip: on the CPU it exits
    nonzero and prints no record rather than a CPU rate."""
    from heat_tpu.cli import main

    assert main(["bench", "--n", "64", "--steps", "8", "--repeats", "1"]) == 1
    out, err = capsys.readouterr()
    assert "needs a TPU" in err
    assert not [l for l in out.splitlines() if l.startswith("{")]


def test_cli_crash_resume_flow(tmp_cwd, capsys):
    """The elastic-recovery story (SURVEY SS5: the reference ignores its
    MPI error codes entirely): a run dies mid-job, the operator re-issues
    the SAME command, and the solve resumes from the latest checkpoint
    instead of restarting — no flags beyond --checkpoint-every, no
    in-process retry loop (a dead backend needs a fresh process anyway,
    see TROUBLESHOOTING.md)."""
    (tmp_cwd / "input.dat").write_text("16 0.25 0.05 2.0 6 1\n")
    args = ["run", "--backend", "serial", "--dtype", "float64",
            "--checkpoint-every", "2"]

    # the "crashed" first attempt: same config, killed after step 4
    # (emulated by a shorter ntime writing the same checkpoint stream)
    from heat_tpu.backends import solve as _solve
    from heat_tpu.config import HeatConfig, parse_input

    cfg = parse_input("input.dat").with_(backend="serial", dtype="float64",
                                         checkpoint_every=2)
    _solve(cfg.with_(ntime=4))          # dies "mid-run" at step 4
    capsys.readouterr()

    assert main(args) == 0              # operator re-runs the same command
    out = capsys.readouterr().out
    assert "resumed from" in out        # picked up at step 4, not step 0
    # and the result equals an uninterrupted 6-step run
    import numpy as np
    from heat_tpu.io import read_dat

    _, T = read_dat("soln.dat")
    clean = _solve(HeatConfig(n=16, ntime=6, dtype="float64",
                              backend="serial"))
    np.testing.assert_allclose(T, clean.T, rtol=0, atol=1e-12)


def test_viz_3d_midplane(tmp_cwd):
    """The 3-D extension's quadruplet files render as the mid-plane slice
    (the reference has no 3-D viz to imitate)."""
    pytest.importorskip("matplotlib")
    (tmp_cwd / "input.dat").write_text("12 0.15 0.05 2.0 2 1\n")
    assert main(["run", "--backend", "serial", "--dtype", "float64",
                 "--ndim", "3"]) == 0
    assert main(["viz", "soln.dat", "--ndim", "3", "--save", "s3.png"]) == 0
    assert (tmp_cwd / "s3.png").stat().st_size > 0


def test_variant_serial_default_int_dat_and_heartbeat(input_dat, capsys):
    """Default-behavior parity (VERDICT r2 missing #3): every single-process
    Fortran variant writes int.dat unconditionally before solving
    (fortran/serial/heat.f90:50-55) and prints time_it every step (:62) —
    the presets must do both without extra flags."""
    rc = main(["run", "--variant", "serial"])
    assert rc == 0
    assert (input_dat / "int.dat").exists()
    out = capsys.readouterr().out
    assert out.count("time_it:") == 5  # ntime=5, every step


def test_variant_default_opt_outs(input_dat, capsys):
    rc = main(["run", "--variant", "serial", "--no-write-int",
               "--heartbeat-every", "0"])
    assert rc == 0
    assert not (input_dat / "int.dat").exists()
    assert "time_it" not in capsys.readouterr().out


def test_variant_python_serial_no_int_dat(input_dat, capsys):
    """The python reference variants write no int.dat and print no
    heartbeat (python/serial/heat.py plots inline instead) — their presets
    must not invent either."""
    rc = main(["run", "--variant", "python_serial"])
    assert rc == 0
    assert not (input_dat / "int.dat").exists()
    assert "time_it" not in capsys.readouterr().out


def test_cfl_stability_warning(tmp_cwd, capsys):
    """VERDICT r2 missing #4: sigma above 1/(2*ndim) warns loudly on the
    run path (a warning, not an error — the reference admits unstable
    configs; FTCS bound per fortran/serial/heat.f90:15-17)."""
    (tmp_cwd / "input.dat").write_text("16 0.25 0.05 2.0 2 0\n")
    # sigma=0.25 == the 2D bound: stable, silent
    assert main(["run", "--backend", "serial", "--dtype", "float64"]) == 0
    assert "stability bound" not in capsys.readouterr().out
    # same sigma in 3D exceeds 1/6: loud warning, run still completes
    assert main(["run", "--backend", "serial", "--dtype", "float64",
                 "--ndim", "3"]) == 0
    out = capsys.readouterr().out
    assert "WARNING" in out and "1/(2*ndim)" in out
    # sigma=0.15 < 1/6: silent again
    (tmp_cwd / "input.dat").write_text("16 0.15 0.05 2.0 2 0\n")
    assert main(["run", "--backend", "serial", "--dtype", "float64",
                 "--ndim", "3"]) == 0
    assert "stability bound" not in capsys.readouterr().out
    # plan warns the same way, without touching devices
    (tmp_cwd / "input.dat").write_text("16 0.25 0.05 2.0 2 0\n")
    assert main(["plan", "--backend", "serial", "--ndim", "3"]) == 0
    assert "stability bound" in capsys.readouterr().out


def test_cli_bench_on_tpu_prints_one_pass_ratio_and_record(capsys,
                                                           monkeypatch):
    """On a TPU the human line carries the ratio to the one-pass HBM
    bound (a speedup, not a roofline share) and the JSON
    record keeps the bench.py field contract (measurement stubbed: the
    suite is CPU-pinned)."""
    import json

    import jax

    from heat_tpu import benchmark

    rec = {"metric": benchmark.metric_name(64), "value": 2e11,
           "unit": "points/s", "vs_baseline": 2.0, "raw_single_call": 1e11,
           "platform": "tpu", "baseline_chip": "v5e"}
    seen = {}

    def fake_measure(n, steps, repeats):
        seen.update(n=n, steps=steps, repeats=repeats)
        return rec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(benchmark, "headline_measure", fake_measure)
    assert main(["bench", "--repeats", "1"]) == 0
    assert seen == {"n": benchmark.N, "steps": benchmark.STEPS, "repeats": 1}
    out = capsys.readouterr().out
    assert "2.00x the one-pass v5e HBM bound" in out
    assert json.loads(out.strip().splitlines()[-1]) == rec