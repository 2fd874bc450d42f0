"""Cross-backend oracle equivalence — every device backend must reproduce the
numpy oracle (the cross-variant consistency the reference never automated,
SURVEY.md §4)."""

import numpy as np
import pytest

from heat_tpu.backends import get_backend, solve
from heat_tpu.config import HeatConfig


BASE = HeatConfig(n=32, ntime=20, dtype="float64", backend="serial")


def _oracle(cfg):
    return solve(cfg.with_(backend="serial"))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("bc,ic", [("edges", "hat"), ("ghost", "uniform")])
def test_backend_matches_oracle_f64(backend, bc, ic):
    cfg = BASE.with_(bc=bc, ic=ic)
    expect = _oracle(cfg)
    got = solve(cfg.with_(backend=backend))
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=0)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_backend_f32(backend):
    cfg = BASE.with_(dtype="float32", ntime=30)
    expect = _oracle(cfg)
    got = solve(cfg.with_(backend=backend))
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=5e-6)


def test_bf16_storage_f32_accumulate():
    """bf16 runs stay stable and land near the f32 answer."""
    cfg = BASE.with_(dtype="bfloat16", n=64, ntime=25)
    ref = solve(cfg.with_(backend="serial", dtype="float32"))
    got = solve(cfg.with_(backend="xla"))
    assert got.T.dtype == np.float32 or got.T.dtype.name == "bfloat16"
    np.testing.assert_allclose(
        np.asarray(got.T, np.float32), ref.T, rtol=0, atol=3e-2
    )


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_3d_backends(backend):
    cfg = HeatConfig(n=16, ndim=3, ntime=6, dtype="float64", ic="hat",
                     sigma=0.15)
    expect = _oracle(cfg)
    got = solve(cfg.with_(backend=backend))
    # XLA may reassociate the 7-point sum: allow ~1 ulp
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=1e-14)


def test_pallas_availability():
    from heat_tpu.ops.pallas_stencil import pallas_available

    assert pallas_available((256, 256), np.float32)
    assert pallas_available((100, 100), np.float32)      # internal padding
    assert pallas_available((130, 130), np.float32)      # ghost-padded sizes
    assert pallas_available((256, 128, 128), np.float32)
    assert pallas_available((100, 100, 100), np.float32)  # 3D padding too
    assert not pallas_available((256, 256), np.float64)  # no f64 on TPU VPU


def test_pallas_3d_multistep_matches_sequential():
    import jax.numpy as jnp

    from heat_tpu.grid import initial_condition
    from heat_tpu.ops.pallas_stencil import (
        ftcs_multistep_edges_pallas,
        ftcs_step_edges_pallas,
    )

    cfg = HeatConfig(n=24, ndim=3, dtype="float32", ic="hat", sigma=0.15)
    T = jnp.asarray(initial_condition(cfg), jnp.float32)
    seq = T
    for _ in range(5):
        seq = ftcs_step_edges_pallas(seq, cfg.r)
    fused = ftcs_multistep_edges_pallas(T, cfg.r, 5)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(seq),
                               rtol=0, atol=1e-6)


def test_pallas_on_unaligned_shape_matches_oracle():
    """Non-128-multiple grids run through the kernel via padding."""
    cfg = HeatConfig(n=100, ntime=9, dtype="float32", ic="hat")
    expect = solve(cfg.with_(backend="serial"))
    got = solve(cfg.with_(backend="pallas", fuse_steps=4))
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=5e-6)


def test_pallas_kernel_on_tileable_shape():
    cfg = HeatConfig(n=128, ntime=10, dtype="float32", ic="hat")
    expect = solve(cfg.with_(backend="xla"))
    got = solve(cfg.with_(backend="pallas"))
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=1e-6)


def test_multistep_kernel_matches_sequential():
    """Temporal blocking must be step-for-step identical to sequential."""
    import jax.numpy as jnp

    from heat_tpu.grid import initial_condition
    from heat_tpu.ops.pallas_stencil import (
        ftcs_multistep_edges_pallas,
        ftcs_multistep_ghost_pallas,
        ftcs_step_edges_pallas,
        ftcs_step_ghost_pallas,
    )

    cfg = HeatConfig(n=128, dtype="float32", ic="hat")
    T = jnp.asarray(initial_condition(cfg), jnp.float32)
    seq = T
    for _ in range(4):
        seq = ftcs_step_edges_pallas(seq, cfg.r)
    fused = ftcs_multistep_edges_pallas(T, cfg.r, 4)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(seq),
                               rtol=0, atol=1e-6)

    seq_g = T
    for _ in range(3):
        seq_g = ftcs_step_ghost_pallas(seq_g, cfg.r, 1.0)
    fused_g = ftcs_multistep_ghost_pallas(T, cfg.r, 1.0, 3)
    np.testing.assert_allclose(np.asarray(fused_g), np.asarray(seq_g),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("bc,ic", [("edges", "hat"), ("ghost", "uniform")])
def test_pallas_backend_with_fusion_matches_oracle(bc, ic):
    cfg = HeatConfig(n=128, ntime=21, dtype="float32", ic=ic, bc=bc,
                     backend="pallas", fuse_steps=4)  # 5 fused passes + 1
    expect = solve(cfg.with_(backend="serial"))
    got = solve(cfg)
    np.testing.assert_allclose(got.T, expect.T, rtol=0, atol=5e-6)


@pytest.mark.parametrize("n,bc,dtype", [(40, "edges", "float32"),
                                        (64, "ghost", "float32"),
                                        (40, "periodic", "float32"),
                                        (64, "edges", "bfloat16")])
@pytest.mark.parametrize("rem", [0, 2])
@pytest.mark.parametrize("n_fused", range(5))
def test_pallas_advance_is_the_sequential_passes(n, bc, dtype, rem, n_fused):
    """The backend's fused loop (two passes an iteration, an odd pass
    peeled) runs the same kernel calls in the same order as calling the
    BC's multistep ``n_fused`` times and its single step ``rem`` times:
    the answer is bit for bit the same."""
    import jax.numpy as jnp

    from heat_tpu.backends.pallas import make_advance
    from heat_tpu.grid import initial_condition
    from heat_tpu.ops import pallas_stencil as ps

    kf = 3
    cfg = HeatConfig(n=n, dtype=dtype, ic="hat", bc=bc, bc_value=0.5,
                     backend="pallas", fuse_steps=kf)
    r = cfg.r
    multi, step = {
        "edges": (lambda t: ps.ftcs_multistep_edges_pallas(t, r, kf),
                  lambda t: ps.ftcs_step_edges_pallas(t, r)),
        "ghost": (lambda t: ps.ftcs_multistep_ghost_pallas(t, r, 0.5, kf),
                  lambda t: ps.ftcs_step_ghost_pallas(t, r, 0.5)),
        "periodic": (lambda t: ps.ftcs_multistep_periodic_pallas(t, r, kf),
                     lambda t: ps.ftcs_step_periodic_pallas(t, r)),
    }[bc]
    T = jnp.asarray(initial_condition(cfg), dtype)
    expect = T
    for _ in range(n_fused):
        expect = multi(expect)
    for _ in range(rem):
        expect = step(expect)
    got = make_advance(cfg)(jnp.copy(T), n_fused * kf + rem)
    got, expect, T = (np.asarray(a, np.float32) for a in (got, expect, T))
    np.testing.assert_array_equal(got, expect)
    if n_fused or rem:
        assert not np.array_equal(got, T)


def test_multistep_fallback_when_k_exceeds_tile():
    import jax.numpy as jnp

    from heat_tpu.ops.pallas_stencil import (
        ftcs_multistep_edges_pallas,
        ftcs_step_edges_pallas,
    )

    T = jnp.ones((16, 128), jnp.float32).at[8, 60:70].set(2.0)
    # tile for a 16-row grid is at most 16 < 32 -> sequential fallback
    fused = ftcs_multistep_edges_pallas(T, 0.25, 32)
    seq = T
    for _ in range(32):
        seq = ftcs_step_edges_pallas(seq, 0.25)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(seq),
                               rtol=0, atol=1e-6)


def test_heartbeat_and_zero_steps():
    cfg = BASE.with_(ntime=0)
    res = solve(cfg.with_(backend="xla"))
    np.testing.assert_array_equal(res.T, _oracle(cfg).T)
    res = solve(BASE.with_(backend="xla", ntime=7, heartbeat_every=3))
    assert res.timing.steps == 7


def test_unknown_backend():
    with pytest.raises(KeyError):
        get_backend("nccl")


def test_warm_exec_and_fetch_flags():
    cfg = HeatConfig(n=48, ntime=8, dtype="float32", backend="xla")
    plain = solve(cfg)
    warm = solve(cfg, warm_exec=True)
    np.testing.assert_allclose(warm.T, plain.T, rtol=0, atol=0)
    nofetch = solve(cfg, fetch=False)
    assert nofetch.T is None
    assert nofetch.timing.solve_s > 0


def test_async_shard_checkpoints_multihost(tmp_path, monkeypatch):
    """ISSUE 1: the multi-host ``save_shards`` path under the async
    pipeline — snapshot-and-continue must write the same per-process shard
    files as the sync path, and the resume must rebuild from them. Faked
    multi-host via the ``_addressable`` seam (tests/test_multihost.py)."""
    import heat_tpu.backends.common as common
    from heat_tpu.runtime import checkpoint

    monkeypatch.setattr(common, "_addressable", lambda x: False)
    da, ds = tmp_path / "async", tmp_path / "sync"
    cfg = HeatConfig(n=16, ntime=4, dtype="float32", backend="sharded",
                     mesh_shape=(2, 2), checkpoint_every=2)
    ra = solve(cfg.with_(checkpoint_dir=str(da)))
    assert ra.timing.overlap_s is not None     # the writer really ran
    solve(cfg.with_(checkpoint_dir=str(ds), async_io="off"))
    names = sorted(p.name for p in da.glob("heat_shards_step*.npz"))
    assert names == ["heat_shards_step00000002.proc0000.npz",
                     "heat_shards_step00000004.proc0000.npz"]
    for step in (2, 4):
        ba, sa = checkpoint.load_shards(cfg.with_(checkpoint_dir=str(da)),
                                        step)
        bs, ss = checkpoint.load_shards(cfg.with_(checkpoint_dir=str(ds)),
                                        step)
        assert sa == ss == step
        for (off_a, blk_a), (off_s, blk_s) in zip(ba, bs):
            assert off_a == off_s
            np.testing.assert_array_equal(blk_a, blk_s)
    # resume from the async-written shard files, bit-identical to a clean run
    res = solve(cfg.with_(checkpoint_dir=str(da), ntime=6))
    assert res.start_step == 4
    clean = solve(cfg.with_(ntime=6, checkpoint_every=0))
    np.testing.assert_array_equal(np.asarray(res.T_dev),
                                  np.asarray(clean.T_dev))


def test_async_checkpoint_solve_matches_plain_solve(tmp_path):
    """The snapshot copy must be donation-safe: a checkpointing async run's
    final field is bit-identical to a run with no checkpoints at all, on
    both single-device and sharded backends."""
    for backend, mesh in (("xla", None), ("sharded", (2, 2))):
        cfg = HeatConfig(n=16, ntime=8, dtype="float64", backend=backend,
                         mesh_shape=mesh)
        plain = solve(cfg)
        ck = solve(cfg.with_(checkpoint_every=2,
                             checkpoint_dir=str(tmp_path / backend)))
        np.testing.assert_array_equal(ck.T, plain.T)


def test_bounded_pallas_kernel_contract():
    """Bounded kernel with a discard margin >= ksteps reproduces the plain
    frozen-ring kernel on the interior it owns."""
    import jax.numpy as jnp

    from heat_tpu.ops.pallas_stencil import (
        _multistep,
        ftcs_multistep_bounded_pallas,
    )

    rng = np.random.default_rng(3)
    w = 4
    T = jnp.asarray(rng.random((40, 40)), jnp.float32)
    # reference: plain kernel freezes ring-1 of the same array
    ref = _multistep(T, 0.2, w)
    bounds = jnp.asarray([0, 39, 0, 39], jnp.int32)
    got = ftcs_multistep_bounded_pallas(T, 0.2, w, bounds)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=0, atol=0)
    # freeze-nothing bounds: only the interior >= w cells from every edge is
    # trustworthy (the caller's discard margin)
    open_bounds = jnp.asarray([-1, 40, -1, 40], jnp.int32)
    got2 = ftcs_multistep_bounded_pallas(T, 0.2, w, open_bounds)
    # compare against w unconstrained FTCS steps computed densely in numpy
    dense = np.asarray(T, np.float64)
    for _ in range(w):
        nxt = dense.copy()
        nxt[1:-1, 1:-1] = dense[1:-1, 1:-1] + 0.2 * (
            dense[2:, 1:-1] + dense[:-2, 1:-1] + dense[1:-1, 2:]
            + dense[1:-1, :-2] - 4 * dense[1:-1, 1:-1])
        dense = nxt
    inner = slice(w, -w)
    np.testing.assert_allclose(np.asarray(got2)[inner, inner],
                               dense[inner, inner], rtol=0, atol=2e-6)
