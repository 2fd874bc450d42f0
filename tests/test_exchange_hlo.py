"""Compiled-HLO guard for the halo exchange (round 3).

The indep ghost-write formulation (parallel/halo.py
``halo_exchange_indep``) exists to keep a full-local-shard copy out of
the compiled multi-device advance: the sequential form, whose axes chain
through the updated array, compiled to two. This pins that property at
compile level so a refactor can't silently bring the copy back: on the
4x2 virtual mesh the padded-carry advance carries at most the one
loop-structural full-shard copy it compiles to today.
"""

import re

import jax.numpy as jnp
import pytest

from heat_tpu.backends.sharded import make_padded_carry_machinery
from heat_tpu.config import HeatConfig
from heat_tpu.parallel.mesh import build_mesh


def _full_shape_copies(txt: str, shape: str) -> int:
    return len(re.findall(rf"=\s*{re.escape(shape)}\S*\s+copy\(", txt))


@pytest.mark.parametrize("fuse", [1, 4])
def test_indep_advance_has_fewer_fullshard_copies(fuse):
    n = 64
    mesh = build_mesh(2, (4, 2))
    cfg = HeatConfig(n=n, ntime=8, dtype="float32", backend="sharded",
                     mesh_shape=(4, 2), fuse_steps=fuse)
    seed, advance, _ = make_padded_carry_machinery(cfg, mesh)
    Tp = seed(jnp.zeros((n, n), jnp.float32))
    txt = advance.lower(Tp, 8).compile().as_text()
    # the padded local shard: (n/4 + 2w, n/2 + 2w)
    w = fuse
    shape = f"f32[{n // 4 + 2 * w},{n // 2 + 2 * w}]"
    copies = _full_shape_copies(txt, shape)
    # calibrated against the CPU XLA pipeline of jax 0.9.x / jaxlib 0.9.x,
    # where the sequential exchange compiled to 2: if a jax upgrade trips
    # this while the exchange source is unchanged, re-calibrate the pin
    assert copies <= 1, copies  # the one loop-structural copy
