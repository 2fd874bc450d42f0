"""The sharded backend's Pallas path: every fused block exchanges the
halo (``halo_exchange_indep``), then each shard steps with the bounded
multistep kernel (interpret mode on CPU). Held to the float64 serial
oracle across boundary conditions, meshes and ranks, the remainder
chunk, shards no wider than twice the halo, and the staged exchange, at
the tolerance of test_sharded.py's Pallas cases (f32 storage). bf16
storage is held to the XLA local kernel instead: the two round
intermediates differently, so it gets a bf16 tolerance.
"""

import numpy as np
import pytest

from heat_tpu.backends import solve
from heat_tpu.config import HeatConfig


def _matches_serial(cfg):
    want = solve(cfg.with_(backend="serial", mesh_shape=None,
                           dtype="float64")).T
    got = np.asarray(solve(cfg).T)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


BASE = dict(dtype="float32", backend="sharded", local_kernel="pallas")


@pytest.mark.parametrize("bc", ["edges", "ghost", "periodic"])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2), (4, 2)])
def test_sharded_pallas_2d_matches_serial(bc, mesh_shape):
    _matches_serial(HeatConfig(n=64, ntime=12, bc=bc, mesh_shape=mesh_shape,
                               fuse_steps=4, **BASE))


@pytest.mark.parametrize("bc", ["edges", "periodic"])
def test_sharded_pallas_3d_matches_serial(bc):
    _matches_serial(HeatConfig(n=32, ndim=3, ntime=6, bc=bc, sigma=1 / 6,
                               mesh_shape=(2, 2, 2), fuse_steps=2, **BASE))


def test_sharded_pallas_remainder_chunk():
    # ntime % fuse != 0: the remainder block runs ksteps < wpad (margins
    # stay wpad-wide)
    _matches_serial(HeatConfig(n=64, ntime=10, mesh_shape=(2, 2),
                               fuse_steps=4, **BASE))


@pytest.mark.parametrize("n,fuse", [
    (16, 8),   # L = 8 = w: the halo is as deep as the shard
    (24, 8),   # w < L=12 < 2w
    (32, 8),   # L = 16 = 2w exactly
])
def test_sharded_pallas_tiny_shards(n, fuse):
    _matches_serial(HeatConfig(n=n, ntime=8, mesh_shape=(2, 2),
                               fuse_steps=fuse, **BASE))


def test_sharded_pallas_matches_serial_oracle():
    _matches_serial(HeatConfig(n=48, ntime=10, mesh_shape=(2, 2),
                               fuse_steps=4, **BASE))


def test_sharded_pallas_bf16_close_to_xla_kernel():
    cfg = HeatConfig(n=64, ntime=8, dtype="bfloat16", backend="sharded",
                     local_kernel="pallas", mesh_shape=(2, 2), fuse_steps=4)
    ref = np.asarray(solve(cfg.with_(local_kernel="xla")).T, np.float32)
    got = np.asarray(solve(cfg).T, np.float32)
    np.testing.assert_allclose(got, ref, rtol=3e-2, atol=3e-2)


def test_sharded_pallas_staged_comm():
    # the HIP-preset staged (host round-trip) exchange composes with the
    # Pallas local kernel
    _matches_serial(HeatConfig(n=64, ntime=8, comm="staged",
                               mesh_shape=(2, 2), fuse_steps=4, **BASE))
