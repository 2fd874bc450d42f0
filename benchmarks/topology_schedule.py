"""Multi-chip schedule census via AOT topology compile — no chip needed.

A 1x1 mesh can't show where the sharded advance's halo collectives sit
in the schedule (ppermute degenerates), and multi-chip hardware isn't
attached — but ``jax.experimental.topologies`` compiles a GENUINE
multi-chip TPU executable on a CPU-only host (the Mosaic + XLA:TPU
compilers ship in libtpu and need no device), so the schedule is
readable from the compiled module's order:

- async ``collective-permute-start``/``-done`` pairs (TPU lowering of the
  ppermutes), and
- how many Mosaic ``custom-call`` kernels are scheduled strictly inside
  a start->done flight window (>0 = kernel work overlaps the wire time).

Compiled-module text is in schedule order for TPU, so "inside the
window" is the scheduler's actual decision, not an inference. Measured
first run (v5e:2x4, 4x2 mesh, 1024^2, fuse 4): 1 kernel call, 0
in-window (strictly exchange-then-kernel).

Run anywhere, no chip needed: ``python benchmarks/topology_schedule.py``
Writes benchmarks/topology_schedule.json (atomic); its one row is
``rows["indep"]``, the exchange's name in the older records.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from _util import write_atomic  # noqa: E402


def schedule_census(txt: str) -> dict:
    """Per-flight-window schedule analysis of a compiled TPU module.

    Windows are matched exactly: each ``collective-permute-done`` names
    its ``-start`` as an operand, so every (start, done) pair is the real
    flight window even when windows interleave (start1 start2 done1
    done2 — the shape a latency-hiding schedule produces). A kernel
    counts as in-flight iff its line sits strictly inside SOME matched
    window (kernels between disjoint windows don't count)."""
    import re

    lines = txt.splitlines()
    # op DEFINITIONS only (`%name = ... collective-permute-start(...)`):
    # fusion lines that merely take a start/done as an operand must not
    # count as windows
    start_def = re.compile(r"\s*(\S+?)\s*=.*\scollective-permute-start\(")
    done_def = re.compile(r"\s*\S+\s*=.*\scollective-permute-done\((.*)")
    start_idx = {}
    for i, ln in enumerate(lines):
        m = start_def.match(ln)
        if m:
            start_idx[m.group(1).lstrip("%")] = i
    windows = []
    unmatched = 0
    for i, ln in enumerate(lines):
        m = done_def.match(ln)
        if not m:
            continue
        # Printer-robust operand parse (the custom_call_census lesson: a
        # regex tuned to one HLO printer silently records zeros on the
        # next). Newer printers annotate the operand with its full
        # tuple type — "done((f32[4,40]{1,0:T(4,128)S(1)}, ...)
        # %collective-permute-start.1)" — so a [^)]* capture eats layout
        # tokens, never the name. SSA names are the only %-prefixed
        # tokens on the line; older printers spell operands bare, so
        # fall back to the comma-split form when no %-token appears.
        ops = [o.lstrip("%") for o in re.findall(r"%[\w.\-#]+", m.group(1))]
        if not ops:
            ops = [o.strip() for o in m.group(1).rstrip(")").split(",")]
        s = next((start_idx[o] for o in ops if o in start_idx), None)
        if s is None:
            unmatched += 1
        else:
            windows.append((s, i))
    customs = [i for i, ln in enumerate(lines) if "custom-call" in ln]
    per_window = [sum(1 for c in customs if s < c < d) for s, d in windows]
    in_flight = {c for c in customs for s, d in windows if s < c < d}
    # each in-flight kernel by the name its instruction carries, without
    # the compiler's numeric suffix (``%heat_halo_cut.4`` -> heat_halo_cut)
    names = sorted(re.sub(r"\.\d+$", "", m.group(1)) for c in in_flight
                   if (m := re.match(r"\s*%([\w.\-]+)", lines[c])))
    return {
        "async_pairs": len(windows),
        "unmatched_dones": unmatched,
        "custom_calls": len(customs),
        "kernels_in_flight": len(in_flight),
        "kernels_in_flight_names": names,
        "kernels_in_flight_per_window": per_window,
        "copies": txt.count(" copy("),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x4",
                    help="TPU topology name for the AOT compile")
    ap.add_argument("--mesh", default="4x2")
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--fuse", type=int, default=4)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--out", default="topology_schedule.json",
                    help="output filename (under benchmarks/) — flagship-"
                         "shape runs must not clobber the toy-scale row")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")  # works chipless by design
    from jax.experimental import topologies

    from heat_tpu.backends.sharded import (_probe_state_struct,
                                           fuse_depth_sharded,
                                           make_padded_carry_machinery)
    from heat_tpu.config import HeatConfig
    from heat_tpu.ops.pallas_stencil import force_compiled_kernels
    from heat_tpu.parallel.mesh import build_mesh  # noqa: F401 (parity cite)

    mesh_shape = tuple(int(v) for v in args.mesh.split("x"))
    if len(mesh_shape) not in (2, 3):
        # ndim follows the mesh rank below; a 1-axis mesh would need a
        # separate field-rank flag this census has never exercised (the
        # old code also built a rank-1 padded struct for it and crashed
        # later) — fail clearly at the argument instead
        ap.error(f"--mesh must be 2-D or 3-D (AxB or AxBxC), got "
                 f"{args.mesh!r}")
    topo = topologies.get_topology_desc(args.topology, "tpu")
    mesh = topologies.make_mesh(topo, mesh_shape,
                                tuple("xyz"[: len(mesh_shape)]))

    out = Path(__file__).parent / args.out
    rec = {"ts": time.time(), "topology": args.topology,
           "mesh": list(mesh_shape), "n": args.n, "fuse": args.fuse,
           "steps": args.steps, "rows": {}}

    with force_compiled_kernels():
        # ndim follows the mesh rank (a 2x2x2 --mesh censuses the 3D
        # advance)
        cfg = HeatConfig(n=args.n, ndim=len(mesh_shape), ntime=args.steps,
                         dtype="float32", backend="sharded",
                         mesh_shape=mesh_shape, fuse_steps=args.fuse,
                         local_kernel="pallas")
        _, advance, _ = make_padded_carry_machinery(cfg, mesh)
        struct = _probe_state_struct(
            cfg, mesh, fuse_depth_sharded(cfg, mesh_shape), padded=True)
        t0 = time.perf_counter()
        txt = advance.lower(struct, args.steps).compile().as_text()
        row = schedule_census(txt)
        row["compile_s"] = time.perf_counter() - t0
        rec["rows"]["indep"] = row
        print(f"pairs={row['async_pairs']} kernels={row['custom_calls']} "
              f"in-flight={row['kernels_in_flight']} "
              f"(per-window {row['kernels_in_flight_per_window']}) "
              f"copies={row['copies']} "
              f"[compile {row['compile_s']:.0f}s]", flush=True)
        write_atomic(out, rec)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
