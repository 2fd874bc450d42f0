"""AOT topology compile: the sharded advance's schedule, pinned chiplessly.

``jax.experimental.topologies`` compiles genuine multi-chip TPU
executables on a CPU-only host (XLA:TPU + Mosaic ship in libtpu), which
makes where the kernels sit against the async collective-permute flight
windows a testable property rather than an on-hardware observation.
Skips cleanly where no TPU AOT compiler is available."""

import jax
import pytest

# Topology-AOT executables can't round-trip the persistent compile cache
# (read side: libtpu's "DeserializeLoadedExecutable not implemented"; write
# side: a CompileOnlyPyClient's Executable isn't serializable); jax
# surfaces each failed read/write as a UserWarning per compile and then
# compiles normally — harmless here, and consistent with the measured fact
# that Mosaic topology compiles bypass the persistent cache entirely
# (TROUBLESHOOTING.md "Compiles"). Filter exactly those messages so
# `pytest -q` stays clean (VERDICT r5 #8); any OTHER warning still
# surfaces.
pytestmark = pytest.mark.filterwarnings(
    "ignore:Error (reading|writing) persistent compilation cache entry"
    ":UserWarning")

from heat_tpu.backends.sharded import (  # noqa: E402
    _probe_state_struct, make_padded_carry_machinery)
from heat_tpu.config import HeatConfig
from heat_tpu.ops.pallas_stencil import force_compiled_kernels


@pytest.fixture(scope="module")
def topo_mesh():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu AOT compiler on this host
        pytest.skip(f"TPU AOT topology compiler unavailable: {e}")
    return topologies.make_mesh(topo, (2, 2), ("x", "y"))


@pytest.fixture(autouse=True)
def _no_x64():
    """The suite runs with x64 on (f64 parity oracle); Mosaic lowering is
    an f32/i32 world — under x64 the roll amounts trace as i64 and fail
    op verification. TPU runs never enable x64, so scope it off here."""
    import jax

    jax.config.update("jax_enable_x64", False)
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_x64", True)
    jax.clear_caches()


def _compiled_text(topo_mesh) -> str:
    n, fuse, steps = 256, 4, 8
    cfg = HeatConfig(n=n, ntime=steps, dtype="float32", backend="sharded",
                     mesh_shape=(2, 2), fuse_steps=fuse,
                     local_kernel="pallas")
    with force_compiled_kernels():
        _, advance, _ = make_padded_carry_machinery(cfg, topo_mesh)
        # the carried state: the padded shards, in the kernel's aligned
        # shape where each steps with the thin-band kernel
        struct = _probe_state_struct(cfg, topo_mesh, fuse, padded=True)
        return advance.lower(struct, steps).compile().as_text()


def _census(txt: str) -> dict:
    # ONE definition of the schedule census — the lab's
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmarks"))
    from topology_schedule import schedule_census

    return schedule_census(txt)


def test_indep_schedule_is_strictly_sequential(topo_mesh):
    c = _census(_compiled_text(topo_mesh))
    assert c["async_pairs"] >= 4
    assert c["unmatched_dones"] == 0
    # exchange-then-kernel: no stencil kernel runs while a slab is on the
    # wire. The exchange's own strip copy (``heat_halo_cut``, which cuts
    # the column slabs from the shard the row slabs were cut from) may.
    assert set(c["kernels_in_flight_names"]) <= {"heat_halo_cut"}
    assert len(c["kernels_in_flight_names"]) == c["kernels_in_flight"]
