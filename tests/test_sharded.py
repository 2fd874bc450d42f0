"""Distributed correctness on the 8-device fake CPU mesh: decomposed runs
must match the undecomposed oracle (golden test, SURVEY.md §4 item 4)."""

import jax
import numpy as np
import pytest

from heat_tpu.backends import solve
from heat_tpu.config import HeatConfig
from heat_tpu.parallel.mesh import auto_mesh_shape, build_mesh, validate_divisible


BASE = HeatConfig(n=32, ntime=12, dtype="float64", backend="sharded")


def test_eight_devices_available():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual CPU devices"


def test_auto_mesh_shape():
    assert auto_mesh_shape(8, 2) == (4, 2)
    assert auto_mesh_shape(16, 2) == (4, 4)   # BASELINE.json config 3
    assert auto_mesh_shape(8, 3) == (2, 2, 2)
    assert auto_mesh_shape(1, 2) == (1, 1)
    assert auto_mesh_shape(6, 2) == (3, 2)


def test_validate_divisible():
    mesh = build_mesh(2, (4, 2))
    validate_divisible(32, mesh)
    with pytest.raises(ValueError):
        validate_divisible(30, mesh)  # 30 % 4 != 0 -> loud failure


@pytest.mark.parametrize("mesh_shape", [(8, 1), (1, 8), (2, 4), (4, 2)])
def test_sharded_matches_serial_edges(mesh_shape):
    """2-D decomposition generalizing the reference's 1-D split
    (fortran/mpi+cuda/heat.F90:87-93); (8,1) IS the reference layout."""
    cfg = BASE.with_(mesh_shape=mesh_shape, bc="edges", ic="hat")
    expect = solve(cfg.with_(backend="serial"))
    got = solve(cfg)
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=0)


@pytest.mark.parametrize("mesh_shape", [(8, 1), (2, 4)])
def test_sharded_matches_serial_ghost(mesh_shape):
    cfg = BASE.with_(mesh_shape=mesh_shape, bc="ghost", ic="uniform")
    expect = solve(cfg.with_(backend="serial"))
    got = solve(cfg)
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=0)


@pytest.mark.parametrize("bc,ic", [("edges", "hat"), ("ghost", "uniform")])
def test_fused_halo_matches_per_step_exchange(bc, ic):
    """Communication-avoiding wide-halo steps == every-step exchange, exactly."""
    cfg = BASE.with_(mesh_shape=(2, 4), bc=bc, ic=ic, ntime=11)
    per_step = solve(cfg.with_(fuse_steps=1))
    fused = solve(cfg.with_(fuse_steps=4))  # 2 fused exchanges + remainder 3
    np.testing.assert_allclose(fused.T, per_step.T, rtol=0, atol=0)
    serial = solve(cfg.with_(backend="serial"))
    np.testing.assert_allclose(fused.T, serial.T, rtol=0, atol=0)


def test_fuse_depth_capped_by_local_extent():
    from heat_tpu.backends.sharded import fuse_depth_sharded

    cfg = BASE.with_(fuse_steps=0)          # auto: k* = sqrt(L/d)
    assert fuse_depth_sharded(cfg, (8, 1)) == round((4 / 2) ** 0.5)
    assert fuse_depth_sharded(cfg, (2, 2)) == round((16 / 2) ** 0.5)
    assert fuse_depth_sharded(cfg.with_(fuse_steps=3), (2, 2)) == 3
    # large local blocks clamp at the kernel's PER-PASS chunk depth (16
    # at flagship width under _thin_chunk_cap): the round-5 on-chip curve
    # measured k=16 12% faster than k=32 once k=32 executes as two
    # 16-deep passes (collective_overhead.json 2026-08-01)
    big = cfg.with_(n=16384, dtype="float32")
    assert fuse_depth_sharded(big, (1, 1)) == 16
    # ... while the xla local kernel (no per-pass chunk) keeps sqrt form
    assert fuse_depth_sharded(big.with_(local_kernel="xla"), (1, 1)) == 32
    # ... as does f64 (BASE's dtype), which always resolves to xla
    assert fuse_depth_sharded(big.with_(dtype="float64"), (1, 1)) == 32
    # and an explicit 32 is still honored
    assert fuse_depth_sharded(big.with_(fuse_steps=32), (1, 1)) == 32
    # near the band threshold the cap must be judged at the GHOST-PADDED
    # width the kernel actually sees: local 4864 reads 32 unpadded but
    # the (4864+2*32)-wide runtime band crosses the cap -> 16 (review r5)
    near = cfg.with_(n=4864, dtype="float32")
    assert fuse_depth_sharded(near, (1, 1)) == 16


def test_sharded_staged_comm_matches_direct():
    """NO_AWARE staged path == CUDA-aware path numerically
    (fortran/mpi+cuda/heat.F90:162-172: same data, different route)."""
    cfg = BASE.with_(mesh_shape=(2, 2), bc="ghost", ic="uniform", ntime=6)
    direct = solve(cfg.with_(comm="direct"))
    staged = solve(cfg.with_(comm="staged"))
    np.testing.assert_allclose(staged.T, direct.T, rtol=0, atol=0)


def test_staged_comm_host_roundtrip_actually_fires(monkeypatch):
    """comm='staged' must really route slabs through host memory — numeric
    equality alone is tautological (the callback is an identity). Count the
    host callbacks: staged fires them per axis per exchange (send + recv
    legs, distinct slab shapes per axis); direct fires none. Fails if
    staged=True silently takes the direct path."""
    import jax as _jax

    from heat_tpu.parallel import halo

    calls = []

    def counting_stage(x):
        def cb(a):
            calls.append(tuple(a.shape))
            return np.asarray(a)

        return _jax.pure_callback(
            cb, _jax.ShapeDtypeStruct(x.shape, x.dtype), x,
            vmap_method="sequential")

    monkeypatch.setattr(halo, "_stage_through_host", counting_stage)
    cfg = BASE.with_(mesh_shape=(2, 2), bc="ghost", ic="uniform", ntime=4,
                     fuse_steps=2)
    solve(cfg.with_(comm="staged"))
    n_exchanges = 2  # ntime=4 at fuse depth 2
    # send+recv legs on both sides of both axes: >= 4 stagings per axis per
    # exchange (per shard on top, but don't over-specify runtime sharding)
    assert len(calls) >= 4 * 2 * n_exchanges, calls
    shapes = set(calls)
    w = 2  # fused halo width
    assert any(s[0] == w and s[1] > w for s in shapes), shapes  # x-axis slabs
    assert any(s[1] == w and s[0] > w for s in shapes), shapes  # y-axis slabs

    calls.clear()
    solve(cfg.with_(comm="direct"))
    assert calls == [], "direct path must never stage through host"


def test_sharded_3d():
    cfg = HeatConfig(n=16, ndim=3, ntime=5, dtype="float64", sigma=0.15,
                     ic="hat", backend="sharded", mesh_shape=(2, 2, 2))
    expect = solve(cfg.with_(backend="serial"))
    got = solve(cfg)
    # 7-point sum reassociation: ~1 ulp
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=1e-14)


def test_sharded_report_sum():
    cfg = BASE.with_(mesh_shape=(4, 2), bc="ghost", ic="uniform",
                     report_sum=True)
    expect = solve(cfg.with_(backend="serial"))
    got = solve(cfg)
    assert got.gsum == pytest.approx(expect.gsum, rel=1e-12)


def test_sharded_f32_and_bf16():
    for dt, atol in [("float32", 1e-6), ("bfloat16", 3e-2)]:
        cfg = BASE.with_(mesh_shape=(2, 4), dtype=dt, ntime=8)
        expect = solve(cfg.with_(backend="serial", dtype="float32"))
        got = solve(cfg)
        np.testing.assert_allclose(
            np.asarray(got.T, np.float32), expect.T, rtol=0, atol=atol
        )


def test_mesh_too_large_rejected():
    with pytest.raises(ValueError):
        build_mesh(2, (16, 16))


@pytest.mark.parametrize("bc", ["edges", "ghost"])
@pytest.mark.parametrize("mesh_shape", [(4, 2), (1, 1)])
def test_sharded_pallas_local_kernel_matches_serial(bc, mesh_shape):
    """The per-shard Pallas fast path (bounded frozen region, interpret mode
    on CPU) must match the serial oracle like the XLA path does."""
    cfg = BASE.with_(mesh_shape=mesh_shape, bc=bc, ic="hat", dtype="float32",
                     local_kernel="pallas", fuse_steps=3)
    expect = solve(cfg.with_(backend="serial", mesh_shape=None,
                             local_kernel="auto"))
    got = solve(cfg)
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=2e-6)


def test_sharded_pallas_matches_xla_local_kernel():
    cfg = BASE.with_(mesh_shape=(2, 4), bc="ghost", ic="uniform",
                     dtype="float32", fuse_steps=4)
    x = solve(cfg.with_(local_kernel="xla"))
    p = solve(cfg.with_(local_kernel="pallas"))
    np.testing.assert_allclose(p.T, x.T, rtol=0, atol=2e-6)


def test_sharded_pallas_3d_matches_serial():
    cfg = HeatConfig(n=16, ndim=3, ntime=6, dtype="float32", sigma=1 / 6,
                     backend="sharded", mesh_shape=(2, 2, 2), bc="ghost",
                     ic="hat", local_kernel="pallas", fuse_steps=2)
    expect = solve(cfg.with_(backend="serial", mesh_shape=None))
    got = solve(cfg)
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=2e-6)


def test_sharded_pallas_f64_rejected_loudly():
    cfg = BASE.with_(mesh_shape=(2, 2), local_kernel="pallas", dtype="float64")
    with pytest.raises(ValueError, match="local_kernel='pallas'"):
        solve(cfg)


def test_report_sum_survives_fetch_false():
    cfg = BASE.with_(mesh_shape=(2, 2), report_sum=True, dtype="float32")
    fetched = solve(cfg)
    nofetch = solve(cfg, fetch=False)
    assert nofetch.gsum is not None
    np.testing.assert_allclose(nofetch.gsum, fetched.gsum, rtol=1e-6)


def test_padded_carry_matches_owned_state_path():
    """The padded-carry fast path (default) and the owned-state path (used
    under checkpointing/numerics-checking) must agree bit-for-bit — same
    exchange, same kernel, same bounds; only the pad/crop placement moves."""
    for bc, ic in (("edges", "hat"), ("ghost", "uniform"),
                   ("periodic", "hat")):
        cfg = BASE.with_(mesh_shape=(2, 4), bc=bc, ic=ic, ntime=9)
        fast = solve(cfg)
        # check_numerics=True forces the owned-state path (and actually
        # checks finiteness along the way)
        classic = solve(cfg.with_(check_numerics=True))
        np.testing.assert_allclose(fast.T, classic.T, rtol=0, atol=0)

    # the Pallas branch of padded_multi too (interpret mode on CPU; f32)
    cfg = BASE.with_(mesh_shape=(2, 4), bc="ghost", ic="uniform", ntime=9,
                     dtype="float32", local_kernel="pallas")
    fast = solve(cfg)
    classic = solve(cfg.with_(check_numerics=True))
    np.testing.assert_allclose(fast.T, classic.T, rtol=0, atol=0)


# 64² over 2x2, float32, each shard stepped by the thin-band kernel
# (interpret mode on CPU): the padded-carry path carries each shard in the
# kernel's aligned shape (backends/sharded.py carried_shapes)
ALIGNED = HeatConfig(n=64, ntime=23, dtype="float32", backend="sharded",
                     mesh_shape=(2, 2), fuse_steps=4, local_kernel="pallas")


def _mesh_2x2():
    return build_mesh(2, (2, 2), devices=jax.devices()[:4])


@pytest.mark.parametrize("bc,ic", [("ghost", "uniform"), ("edges", "hat"),
                                   ("periodic", "hat")])
@pytest.mark.parametrize("fuse,ntime", [(4, 23), (20, 63)])
def test_aligned_carry_matches_owned_state_path(bc, ic, fuse, ntime):
    """The aligned carry and the owned-state path agree bit for bit. At
    fuse 4 the 40² padded shard rides in a 256x128 carry: five fused
    blocks (an odd count, one peeled) and a remainder of 3 that steps the
    carry as it is. At fuse 20 the 72² shard rides in 240x128: three
    blocks and a remainder of 3 whose kernel pass has another geometry,
    so that one pass pads the carry further and slices it back."""
    from heat_tpu.backends.sharded import carried_shapes
    from heat_tpu.ops.pallas_stencil import thin_carry_shape

    cfg = ALIGNED.with_(bc=bc, ic=ic, fuse_steps=fuse, ntime=ntime)
    logical, carried = carried_shapes(cfg, (2, 2), fuse)
    assert logical == (32 + 2 * fuse,) * 2
    assert carried == ((256, 128) if fuse == 4 else (240, 128))
    rem_fits = thin_carry_shape(logical, "float32", ntime % fuse) == carried
    assert rem_fits == (fuse == 4)
    fast = solve(cfg, mesh=_mesh_2x2())
    # check_numerics forces the owned-state path (pad and crop per block)
    classic = solve(cfg.with_(check_numerics=True), mesh=_mesh_2x2())
    np.testing.assert_array_equal(fast.T, classic.T)
    assert np.max(np.abs(fast.T - solve(cfg.with_(ntime=0)).T)) > 1e-3


@pytest.mark.parametrize("bc", ["ghost", "edges", "periodic"])
def test_aligned_carry_keeps_its_alignment_cells_zero(bc):
    """The kernel freezes every cell beyond the logical padded shard, under
    every BC: after 23 steps each 256x128 carry is still zero outside its
    40² shard, as the seed left it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.backends.sharded import make_padded_carry_machinery

    cfg = ALIGNED.with_(bc=bc, ic="hat")
    mesh = _mesh_2x2()
    T0 = (1.0 + np.random.default_rng(5).random((64, 64))).astype(np.float32)
    seed, advance, crop = make_padded_carry_machinery(cfg, mesh)
    Tp = advance(seed(jax.device_put(
        T0, NamedSharding(mesh, P(*mesh.axis_names)))), 23)
    shards = np.asarray(Tp).reshape(2, 256, 2, 128)
    assert np.all(shards[:, 40:] == 0) and np.all(shards[..., 40:] == 0)
    assert np.all(shards[:, :40, :, :40] != 0)
    np.testing.assert_array_equal(np.asarray(crop(Tp)),
                                  solve(cfg, T0=T0, mesh=mesh).T)


def test_mega_advance_on_the_aligned_carry_matches_solo_sharded():
    """A mega-lane (serve's mesh-spanning occupant) steps the same aligned
    carry in chunks of its own; its field equals the solo sharded solve's
    bit for bit, and its boundary reads the chunk's remaining steps."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from heat_tpu.backends.sharded import carried_shapes, make_mega_machinery

    cfg = ALIGNED.with_(bc="ghost", ic="uniform", bc_value=1.0)
    mesh = _mesh_2x2()
    T0 = (1.0 + np.random.default_rng(3).random((64, 64))).astype(np.float32)
    seed, advance, crop, kf = make_mega_machinery(cfg, mesh)
    logical, carried = carried_shapes(cfg, (2, 2), kf)
    assert carried != logical
    Tp = seed(jax.device_put(T0, NamedSharding(mesh, P(*mesh.axis_names))))
    assert Tp.shape == (2 * carried[0], 2 * carried[1])
    rem = jax.numpy.asarray([23], jax.numpy.int32)
    for k in (10, 13):       # 2 fused blocks, rest 1, final step; 3, 0, 1
        Tp, rem, boundary = advance(Tp, rem, k)
    assert int(np.asarray(rem)[0]) == 0
    solo = solve(cfg, T0=T0, mesh=mesh).T
    np.testing.assert_array_equal(np.asarray(crop(Tp)), solo)


def test_fuse_depth_rank_aware_caps():
    """VERDICT r2 weak #5: auto fuse depth must cap at the local kernel's
    per-pass chunk depth for the rank — 3D's kernel chunks at _KMAX_3D=8,
    so exchanging wider pays margin compute on three axes for marginal
    collective savings."""
    from heat_tpu.backends.sharded import fuse_depth_sharded
    from heat_tpu.ops.pallas_stencil import _KMAX_2D, _KMAX_3D

    cfg3 = HeatConfig(n=512, ndim=3, dtype="float32", backend="sharded")
    # sqrt(512/3) ~ 13 would exceed the 3D kernel's chunk depth of 8
    assert fuse_depth_sharded(cfg3, (1, 1, 1)) == _KMAX_3D
    assert fuse_depth_sharded(cfg3, (2, 2, 2)) <= _KMAX_3D
    # 2D clamps at the thin-band per-pass chunk depth at flagship width
    # (round-5 measured optimum; _KMAX_2D=32 remains the EXPLICIT cap)
    cfg2 = HeatConfig(n=16384, ndim=2, dtype="float32", backend="sharded")
    assert fuse_depth_sharded(cfg2, (1, 1)) == 16
    assert fuse_depth_sharded(cfg2.with_(fuse_steps=_KMAX_2D),
                              (1, 1)) == _KMAX_2D
    # explicit requests are honored (capped only by the local extent)
    assert fuse_depth_sharded(cfg3.with_(fuse_steps=16), (1, 1, 1)) == 16
    # tiny local extents still clamp
    assert fuse_depth_sharded(cfg3.with_(n=8), (4, 4, 4)) <= 2


def test_sharded_3d_auto_depth_matches_serial():
    """Pin the 3D auto-depth path end to end: auto fuse (now capped at 8)
    must still bit-match the serial oracle."""
    cfg = HeatConfig(n=16, ndim=3, ntime=10, dtype="float64",
                     backend="sharded", mesh_shape=(2, 2, 2))
    from heat_tpu.backends.sharded import fuse_depth_sharded

    assert 1 < fuse_depth_sharded(cfg, (2, 2, 2)) <= 8
    res = solve(cfg)
    ref = solve(cfg.with_(backend="serial", mesh_shape=None))
    np.testing.assert_array_equal(res.T, ref.T)


def test_build_mesh_cpu_keeps_plain_device_order():
    """Off-TPU, build_mesh is a plain reshape (deterministic shard->device
    binding for the virtual-device tests); the ICI-topology-aware ordering
    (mesh_utils) only engages on real multi-chip TPU."""
    import jax

    from heat_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(2, (4, 2))
    assert [d.id for d in mesh.devices.flat] == [
        d.id for d in jax.devices()[:8]]


# shards (logical padded extent, carrying array, dtype) beside the square
# float64 shards: float32 ones whose array fits the TPU tiles, where the
# column slabs come from the Pallas strip copy, with the high slab inside
# one 128-lane block ([36, 40) of block 0) and straddling two ([126, 130)
# over blocks 0 and 1); and one whose 20 rows are no multiple of the
# sublane tile, where the slabs are cut by an XLA slice
_CUT = ((48, 44), (48, 128), "float32")
_STRADDLE = ((40, 134), (48, 256), "float32")
_ODD_ROWS = ((20, 134), (20, 256), "float32")


@pytest.mark.parametrize("periodic", [False, True])
@pytest.mark.parametrize("mesh_shape,width,shard", [
    pytest.param((4, 2), 1, None, id="mesh_shape0-1"),
    pytest.param((4, 2), 3, None, id="mesh_shape1-3"),
    pytest.param((2, 4), 2, None, id="mesh_shape2-2"),
    pytest.param((2, 2, 2), 2, None, id="mesh_shape3-2"),
    pytest.param((2, 2), 4, _CUT, id="lane_cut"),
    pytest.param((2, 2), 4, _STRADDLE, id="lane_cut_straddle"),
    pytest.param((2, 2), 4, _ODD_ROWS, id="xla_cut_odd_rows"),
])
def test_halo_exchange_indep_bitwise(mesh_shape, width, periodic, shard):
    """halo_exchange_indep must deliver bit-identical ghosts to the
    sequential formulation — including corner/edge regions, whose data
    the indep form forwards by stitching recv slabs instead of re-reading
    the updated array (parallel/halo.py) — whether the column slabs are
    cut by the Pallas strip copy or by an XLA slice, and with the
    alignment cells beyond a logical extent left as they were."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    from heat_tpu.parallel.halo import (halo_exchange, halo_exchange_indep,
                                        lane_cut_plan)

    ndim = len(mesh_shape)
    mesh = build_mesh(ndim, mesh_shape)
    rng = np.random.default_rng(17)
    n = 16
    w = width
    if shard is None:
        logical = None
        local = (n + 2 * w,) * ndim
        dtype = np.float64
    else:
        logical, local, dtype = shard
    cut = lane_cut_plan(local, dtype, w, logical) is not None
    assert cut == (shard in (_CUT, _STRADDLE))
    # per-shard padded arrays, random everywhere (ghosts hold garbage —
    # both formulations must overwrite every ghost cell they claim to)
    gshape = tuple(s * e for s, e in zip(mesh_shape, local))
    G = rng.normal(size=gshape).astype(dtype)
    spec = P(*mesh.axis_names)
    Gd = jax.device_put(G, NamedSharding(mesh, spec))

    outs = {}
    for name, fn in (("seq", halo_exchange), ("indep", halo_exchange_indep)):
        def body(local, fn=fn):
            return fn(local, mesh.axis_names, mesh_shape, 2.0,
                      width=w, periodic=periodic, logical=logical)

        # as on every solve path: the interpreted kernel's outputs carry
        # no mesh-axis variance
        prog = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))
        if name == "indep":
            assert ("heat_halo_cut" in str(prog.trace(Gd).jaxpr)) == cut
        outs[name] = np.asarray(prog(Gd))
    np.testing.assert_array_equal(outs["seq"], outs["indep"])

