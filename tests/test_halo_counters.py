"""What the sharded padded-carry solve counts (runtime/trace.py COUNTERS):
the local kernels' point-steps, and the halo exchanges with the bytes each
device sends, held to the kernel's plan and to the exchange's own
``ppermute``s; and the 2x2 solve against the plain reference and the
float64 oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heat_tpu.backends import sharded, solve
from heat_tpu.config import HeatConfig
from heat_tpu.ops.pallas_stencil import multistep_points
from heat_tpu.parallel.mesh import build_mesh
from heat_tpu.runtime import trace as trace_mod

CFG = HeatConfig(n=64, ntime=12, dtype="float32", backend="sharded",
                 bc="ghost", ic="uniform", fuse_steps=4)
MESHES = [(2, 2), (1, 4), (4, 1)]


def _machinery(cfg):
    mesh = build_mesh(2, cfg.mesh_shape, devices=jax.devices()[:4])
    return mesh, sharded.make_padded_carry_machinery(cfg, mesh)


@pytest.mark.parametrize("local_kernel", ["xla", "pallas"])
@pytest.mark.parametrize("k", [12, 23])     # 3 blocks of 4; 5 and a rest of 3
def test_carried_points_are_the_kernel_plan_over_the_shards(local_kernel, k):
    cfg = CFG.with_(mesh_shape=(2, 2), local_kernel=local_kernel)
    _, (_, advance, _) = _machinery(cfg)
    padded = (32 + 8, 32 + 8)
    blocks = [(4, k // 4)] + ([(k % 4, 1)] if k % 4 else [])
    if local_kernel == "pallas":
        want = 4 * sum(c * multistep_points(padded, "float32", s)
                       for s, c in blocks)
    else:                     # the XLA step updates every padded cell
        want = 4 * 40 * 40 * k
    assert advance.points_computed(k) == want
    assert advance.points_computed(k) > cfg.points * k


def _ppermutes(jaxpr, out):
    """(axis name, perm, slab shape, itemsize) of every ppermute, nested
    jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ppermute":
            aval = eqn.invars[0].aval
            out.append((eqn.params["axis_name"], eqn.params["perm"],
                        aval.shape, aval.dtype.itemsize))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "eqns"):
                    _ppermutes(sub, out)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    _ppermutes(sub.jaxpr, out)
    return out


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_halo_counts_are_the_exchange_slabs(mesh_shape):
    """One fused block holds one exchange: its ``ppermute``s' pairs
    between different shards times their slabs' bytes, over the devices,
    are the counted bytes; an edge shard sends one slab an axis."""
    cfg = CFG.with_(mesh_shape=mesh_shape)
    mesh, (seed, advance, _) = _machinery(cfg)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    Tp = seed(jnp.zeros((64, 64), jnp.float32))
    found = _ppermutes(jax.make_jaxpr(lambda T: advance(T, 4))(Tp).jaxpr,
                       [])
    assert found
    per_device = 0.0
    for axis, perm, shape, item in found:
        axis = axis[0] if isinstance(axis, tuple) else axis
        crossing = sum(s != t for s, t in perm)
        per_device += crossing * int(np.prod(shape)) * item / sizes[axis]
    # the XLA local kernel's 40x40 shard is narrower than a 128-lane
    # block: no exchange cuts its column slabs with the strip copy
    assert advance.halo_counts(4) == (1, round(per_device), 0)
    # 3 blocks of 4; 5 blocks and a rest of 3
    assert advance.halo_counts(12) == (3, 3 * round(per_device), 0)
    assert advance.halo_counts(23) == (6, 6 * round(per_device), 0)


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_counts_at_the_aligned_carry_are_the_logical_shards(mesh_shape):
    """Where each shard steps with the thin-band kernel, the padded-carry
    path carries it in the kernel's aligned array (a 256x128 carry for
    every 64² split here). The counts stay those of the logical padded
    shard: the kernel's point-steps are its plan there, and the exchange
    sends the same slabs as the XLA local kernel's logical carry. The
    jaxpr's own ``ppermute``s still carry the counted bytes."""
    cfg = CFG.with_(mesh_shape=mesh_shape, local_kernel="pallas")
    mesh, (seed, advance, _) = _machinery(cfg)
    logical, carried = sharded.carried_shapes(cfg, mesh_shape, 4)
    assert carried == (256, 128) != logical
    xla = cfg.with_(local_kernel="xla")
    assert sharded.carried_shapes(xla, mesh_shape, 4) == (logical, logical)
    for k in (12, 23):
        blocks = [(4, k // 4)] + ([(k % 4, 1)] if k % 4 else [])
        assert advance.points_computed(k) == 4 * sum(
            c * multistep_points(logical, "float32", s) for s, c in blocks)
        exchanges, sent, cuts = advance.halo_counts(k)
        assert (exchanges, sent) == sharded.carried_halo_counts(
            xla, mesh_shape, 4, k)[:2]
        # the aligned carry fits the tiles: every exchange cuts its
        # column slabs with the strip copy
        assert cuts == exchanges
    Tp = seed(jnp.zeros((64, 64), jnp.float32))
    assert Tp.shape == tuple(c * s for c, s in zip(carried, mesh_shape))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    found = _ppermutes(jax.make_jaxpr(lambda T: advance(T, 4))(Tp).jaxpr,
                       [])
    per_device = 0.0
    for axis, perm, shape, item in found:
        axis = axis[0] if isinstance(axis, tuple) else axis
        crossing = sum(s != t for s, t in perm)
        per_device += crossing * int(np.prod(shape)) * item / sizes[axis]
    assert advance.halo_counts(4) == (1, round(per_device), 1)


def test_halo_bytes_of_the_flagship_2x2():
    """32768^2 on 2x2 at fuse depth 4: every shard has one neighbour an
    axis, and sends it a 4 x 16392 float32 slab."""
    cfg = HeatConfig(n=32768, ntime=1500, dtype="float32",
                     backend="sharded", bc="ghost", fuse_steps=4,
                     mesh_shape=(2, 2))
    # each 16392² shard's last 128-lane block ends past its 16392 columns
    assert sharded.carried_halo_counts(cfg, (2, 2), 4, 1500) == (
        375, 375 * 2 * 4 * 16392 * 4, 0)
    # the same where each shard rides in the kernel's aligned 16464x16512
    pallas = cfg.with_(local_kernel="pallas")
    assert sharded.carried_shapes(pallas, (2, 2), 4) == (
        (16392, 16392), (16464, 16512))
    # whose column slabs, [4, 8) and [16384, 16388), lie in the 128-lane
    # blocks 0 and 128 of its 16512 columns: the strip copy cuts them
    assert sharded.carried_halo_counts(pallas, (2, 2), 4, 1500) == (
        375, 375 * 2 * 4 * 16392 * 4, 375)


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_drive_counts_the_sharded_chunks(mesh_shape):
    trace_mod.configure()
    try:
        cfg = CFG.with_(mesh_shape=mesh_shape, ntime=23,
                        heartbeat_every=12)
        mesh = build_mesh(2, mesh_shape, devices=jax.devices()[:4])
        solve(cfg, mesh=mesh)
        c = trace_mod.get_tracer().counts()
    finally:
        trace_mod.configure()
    _, (_, advance, _) = _machinery(cfg)
    # chunks of 12 and 11 steps (heartbeat interval, then the rest)
    ex12, b12, _ = advance.halo_counts(12)
    ex11, b11, _ = advance.halo_counts(11)
    assert c["heat.halo.exchanges"] == ex12 + ex11 == 3 + 3
    assert c["heat.halo.bytes"] == b12 + b11
    assert c["heat.kernel.points_computed"] == (
        advance.points_computed(12) + advance.points_computed(11))
    assert c["heat.drive.points_useful"] == 64 * 64 * 23


@pytest.mark.parametrize("mesh_shape", MESHES)
@pytest.mark.parametrize("local_kernel,fits", [("pallas", True),
                                               ("xla", False)])
def test_drive_counts_the_lane_cuts(mesh_shape, local_kernel, fits):
    """``heat.halo.lane_cuts`` counts the exchanges whose column slabs the
    Pallas strip copy cut: every exchange where each shard rides in the
    thin-band kernel's aligned 256x128 carry, which fits the tiles, over
    chunks of 12 and 11 steps (the second ends in a remainder block of
    3); none where the XLA local kernel carries the 40x40 logical shard,
    narrower than a 128-lane block."""
    trace_mod.configure()
    try:
        cfg = CFG.with_(mesh_shape=mesh_shape, ntime=23, heartbeat_every=12,
                        local_kernel=local_kernel)
        mesh = build_mesh(2, mesh_shape, devices=jax.devices()[:4])
        solve(cfg, mesh=mesh)
        c = trace_mod.get_tracer().counts()
    finally:
        trace_mod.configure()
    assert c["heat.halo.exchanges"] == 3 + 3
    assert c["heat.halo.lane_cuts"] == (c["heat.halo.exchanges"] if fits
                                        else 0)
    # the count is the program's: its exchange holds the kernel or not
    _, (seed, advance, _) = _machinery(cfg)
    Tp = seed(jnp.zeros((64, 64), jnp.float32))
    jaxpr = jax.make_jaxpr(lambda T: advance(T, 4))(Tp)
    assert ("heat_halo_cut" in str(jaxpr)) == fits


def _seeded(n):
    return (1.0 + np.random.default_rng(7).random((n, n))).astype(np.float64)


@pytest.mark.parametrize("local_kernel", ["xla", "pallas"])
def test_2x2_ghost_fused_solve_agrees_with_reference_and_oracle(
        local_kernel):
    """128^2, ghost walls at 1.0, fuse depth 4 over 4*5+3 steps on 2x2,
    float32: against the plain float32 reference (``perf/reference.py``)
    within 2e-6, a few float32 roundings of fields near 2 (the two sum the
    four neighbours in different orders), and against the float64 oracle
    (``backends/serial_np.py``) within 2e-5, float32's rounding over 23
    steps."""
    from perf import reference

    T0 = _seeded(128)
    cfg = HeatConfig(n=128, ntime=23, dtype="float32", backend="sharded",
                     bc="ghost", bc_value=1.0, fuse_steps=4,
                     mesh_shape=(2, 2), local_kernel=local_kernel)
    mesh = build_mesh(2, (2, 2), devices=jax.devices()[:4])
    got = np.asarray(solve(cfg, T0=T0.astype(np.float32), mesh=mesh).T,
                     np.float64)
    ref = np.asarray(reference.evolve_field(
        jnp.asarray(T0, jnp.float32), cfg.r, 23, "ghost", 1.0))
    oracle = solve(cfg.with_(backend="serial", dtype="float64"), T0=T0).T
    assert np.max(np.abs(got - ref)) <= 2e-6
    assert np.max(np.abs(got - oracle)) <= 2e-5
    # the solve moved the field: not the initial state
    assert np.max(np.abs(got - T0)) > 1e-2
