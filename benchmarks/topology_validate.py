"""Compile-validate the per-chip machine model on every chip class — chiplessly.

VERDICT r3 #4's residual risk: the v4/v5p/v6e entries in
``heat_tpu/machine.py`` are spec-derived ("uncalibrated") — if a VMEM
ceiling or band budget is wrong for a chip, the planner's geometry might
not even compile there. The AOT topology compilers for all four chip
classes ship in libtpu, so that risk is checkable without hardware:

- **Section A (chip tables)**: for each chip class, activate its machine
  model (``machine.override``), let the planners pick geometry for the
  flagship-scale shard, and compile the real sharded advance against
  that chip's topology. Records compile time, the planner's plan string,
  and the compiler's own memory analysis (per-chip argument/output/temp
  bytes — the true VMEM/HBM verdict, not the planner's estimate).
- **Section B (north star)**: BASELINE.json's weak-scaling scenario —
  config 5 (32768^2 bf16+f32acc) on a 16-chip v5p 4x4 mesh, 8192^2
  local block — compiled end to end. The projection's program is now
  compiler-verified, not just arithmetic.

Run (anywhere; no chip): ``python benchmarks/topology_validate.py``
One libtpu process at a time (/tmp/libtpu_lockfile).
Writes benchmarks/topology_validate.json (atomic, incremental).
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from _util import write_atomic  # noqa: E402

# (chip kind for machine.override, topology name, mesh shape)
CASES = [
    ("TPU v5 lite", "v5e:2x2", (2, 2)),
    ("TPU v5", "v5p:2x2x1", (2, 2)),
    ("TPU v4", "v4:2x2x1", (4, 2)),
    ("TPU v6 lite", "v6e:2x2", (2, 2)),
]


def _mem(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
        return {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(m, k)}
    except Exception as e:  # memory analysis is best-effort
        return {"error": f"{type(e).__name__}: {e}"}


def _compile_case(topology, mesh_shape, cfg, steps):
    from jax.experimental import topologies

    from heat_tpu.backends.sharded import (_probe_state_struct,
                                           fuse_depth_sharded,
                                           make_padded_carry_machinery)

    names = tuple("xyz"[: len(mesh_shape)])
    topo = topologies.get_topology_desc(topology, "tpu")
    try:
        mesh = topologies.make_mesh(topo, mesh_shape, names)
    except AssertionError:
        # v4-era topology descriptors expose per-core devices and
        # mesh_utils insists on megacore (one-device-per-chip)
        # granularity. A naive reshape placement is fine here: this lab
        # validates COMPILATION (VMEM/memory verdicts), no wire traffic
        # ever flows.
        import math as _math

        import numpy as _np
        from jax.sharding import Mesh

        need = _math.prod(mesh_shape)
        mesh = Mesh(_np.asarray(topo.devices[:need]).reshape(mesh_shape),
                    names)
    kf = fuse_depth_sharded(cfg, mesh_shape)
    _, advance, _ = make_padded_carry_machinery(cfg, mesh)
    struct = _probe_state_struct(cfg, mesh, kf, padded=True)
    t0 = time.perf_counter()
    compiled = advance.lower(struct, steps).compile()
    return compiled, time.perf_counter() - t0, kf


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--local", type=int, default=4096,
                    help="target local-shard extent for section A (4096 "
                         "keeps per-case Mosaic compiles ~minutes; 8192 "
                         "exercises the thin-band family's capped chunks "
                         "at ~16 min/case — see "
                         "compile_bisect_topology_n8192.json)")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    from heat_tpu import machine
    from heat_tpu.backends.sharded import fuse_depth_sharded
    from heat_tpu.config import HeatConfig
    from heat_tpu.ops.pallas_stencil import (force_compiled_kernels,
                                             plan_summary)

    out = Path(__file__).parent / "topology_validate.json"
    rec = {"ts": time.time(), "local": args.local, "rows": {}}

    with force_compiled_kernels():
        for kind, topology, mesh_shape in CASES:
            machine.override(kind)
            chip = machine.current()
            n = args.local * mesh_shape[0]  # row-axis local = args.local
            cfg = HeatConfig(n=n, ntime=64, dtype="float32",
                             backend="sharded", mesh_shape=mesh_shape,
                             local_kernel="pallas")
            local_shape = tuple(n // s for s in mesh_shape)
            # summarize the LOCAL KERNEL plan at the EXCHANGE fuse depth
            # the compile below actually uses — one k, two labeled
            # concepts (the round-4 artifact described the plan at a
            # hardcoded 32 and recorded the exchange depth under the same
            # word "fuse", reading as a self-contradiction; VERDICT r4 #5)
            kf_ex = fuse_depth_sharded(cfg, mesh_shape)
            row = {"chip": chip.label, "topology": topology,
                   "mesh": list(mesh_shape), "n": n,
                   "plan": plan_summary(local_shape, "float32", kf_ex)}
            try:
                compiled, dt, kf = _compile_case(topology, mesh_shape,
                                                 cfg, 64)
                row.update(compile_s=dt, fuse_exchange=kf,
                           memory=_mem(compiled))
                print(f"{chip.label:20s} {topology:10s} n={n} fuse={kf}: "
                      f"compile {dt:.0f}s  mem={row['memory']}", flush=True)
            except Exception as e:
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                print(f"{chip.label:20s} {topology:10s} FAILED: "
                      f"{row['error'][:160]}", flush=True)
            rec["rows"][f"A_{topology}"] = row
            write_atomic(out, rec)
            machine.override(None)

        # Section B: the BASELINE.json north star, compiler-verified.
        machine.override("TPU v5p")
        cfg5 = HeatConfig(n=32768, ntime=64, dtype="bfloat16",
                          backend="sharded", mesh_shape=(4, 4),
                          local_kernel="pallas")
        kf_ex = fuse_depth_sharded(cfg5, (4, 4))
        row = {"chip": machine.current().label, "topology": "v5p:4x4x1",
               "mesh": [4, 4], "n": 32768, "dtype": "bfloat16",
               "plan": plan_summary((8192, 8192), "bfloat16", kf_ex)}
        try:
            compiled, dt, kf = _compile_case("v5p:4x4x1", (4, 4), cfg5, 64)
            row.update(compile_s=dt, fuse_exchange=kf, memory=_mem(compiled))
            print(f"north-star v5p-16 32768^2 bf16 fuse={kf}: compile "
                  f"{dt:.0f}s  mem={row['memory']}", flush=True)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(f"north-star FAILED: {row['error'][:160]}", flush=True)
        rec["rows"]["B_northstar_v5p16"] = row
        machine.override(None)
        write_atomic(out, rec)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
