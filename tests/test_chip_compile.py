"""The main path's kernels, compiled for a described (not attached) v5e.

The TPU compiler ships with libtpu and compiles for a chip it is only
told about (on-chip-measurement guide §2). Interpret mode cannot see what
it refuses: misaligned slices, VMEM overdraw, programs that do not fit
the device. Each case compiles one kernel at its real width, asserts the
program holds a Mosaic kernel (``tpu_custom_call``) and that the
compiler's memory analysis fits one chip's 16 GiB.

Fusion depths are the deepest that compile in about 15 s here (the
auto depth 16 takes minutes at these widths — PERF.md). The topology is
described inside a fixture, never at import: only one process may load
libtpu, and the driver's workers all import this file."""

import re

import pytest

# Topology compiles cannot round-trip the persistent compile cache; jax
# warns per compile and carries on. Filter exactly those messages.
pytestmark = pytest.mark.filterwarnings(
    "ignore:Error (reading|writing) persistent compilation cache entry"
    ":UserWarning")

_HBM_BYTES = 16 * 2**30   # v5e: 16 GiB of HBM per chip


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu AOT compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from jax.experimental import topologies

    return topologies.make_mesh(topo, (2, 2), ("x", "y"))


@pytest.fixture(autouse=True)
def _compiled_f32():
    """Mosaic lowering is an f32/i32 world and TPU runs never enable x64;
    the suite does (f64 oracle), so scope it off. force_compiled_kernels
    traces Pallas as real Mosaic custom calls off-TPU."""
    import jax

    from heat_tpu.ops.pallas_stencil import force_compiled_kernels

    jax.config.update("jax_enable_x64", False)
    with force_compiled_kernels():
        yield
    jax.config.update("jax_enable_x64", True)
    jax.clear_caches()


def _check(compiled) -> int:
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes
             + m.generated_code_size_in_bytes)
    assert 0 < total < _HBM_BYTES, total
    return total


def _solo(one_chip, n: int, k: int, passes: int = 4):
    import jax
    import jax.numpy as jnp

    from heat_tpu.backends.pallas import make_advance
    from heat_tpu.config import HeatConfig

    cfg = HeatConfig(n=n, ntime=passes * k, dtype="float32", backend="pallas",
                     fuse_steps=k)
    x = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    return make_advance(cfg).lower(x, passes * k).compile()


def _computations(hlo: str) -> dict:
    """The compiled module's computations by name, each as its text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return {k: "\n".join(v) for k, v in comps.items()}


def _ops(text: str, op: str, shape: str) -> list:
    """Names of the ``op`` instructions in ``text`` that produce ``shape``."""
    return re.findall(
        rf"%(\S+) = {re.escape(shape)}\S* {op}(?:-start)?\(", text)


def test_2d_kernel_4096_f32(one_chip):
    """The reference's single-GPU shape (python/cuda/cuda.py:31-33)."""
    total = _check(_solo(one_chip, 4096, 4))
    assert total >= 2 * 4096 * 4096 * 4   # in + out fields at least


@pytest.mark.parametrize("passes", [25, 24])
def test_2d_fused_loop_4096_keeps_no_carry_copy(one_chip, passes):
    """A kernel pass writes a fresh buffer, so a loop of one pass an
    iteration copies it back into the loop carry after every pass (64 MiB
    read and written per pass at 4096²). Two passes an iteration leave the
    loop body without a full-field copy; an odd pass count costs at most
    one copy for the whole program."""
    hlo = _solo(one_chip, 4096, 4, passes).as_text()
    comps = _computations(hlo)
    bodies = re.findall(r"while\(.*?body=%([\w.\-]+)", hlo)
    assert bodies, "no fused loop in the program"
    for body in bodies:
        assert body in comps, body
        assert not _ops(comps[body], "copy", "f32[4096,4096]"), body
        assert comps[body].count("custom-call(") == 2, body
    assert len(_ops(hlo, "copy", "f32[4096,4096]")) <= 1


@pytest.mark.parametrize("steps", [20, 23])
def test_sharded_fused_loop_steps_the_aligned_carry(mesh_2x2, steps):
    """The padded-carry loop of the 2x2 sharded solve, with a padded
    shard whose extent is not the kernel's aligned shape: 2048² over 2x2
    at fuse depth 4 is a 1032² shard (1024 owned plus 4-wide ghosts) in a
    1280x1152 carry (the thin-band plan's 256-row tiles and 128-lane
    columns). 20 steps are five fused blocks; 23 add a remainder of 3.
    Each loop body holds two fused blocks: two in-place exchanges and two
    kernel calls on the carry, with no pad into the kernel's shape, no
    slice of the whole shard back out of it, and no copy of the whole
    shard or carry. Each exchange cuts its column send slabs from two
    128-lane strips that the ``heat_halo_cut`` kernel copies out
    (``parallel/halo.py`` ``cut_lane_slabs``), so layout assignment
    finds no column-major use of the carry to relayout it for. The
    program holds at most one whole copy, outside the loop. (At 512² the
    shard is small enough to stay in the chip's fast memory, and no
    relayout shows.)"""
    from heat_tpu.backends.sharded import (_probe_state_struct,
                                           make_padded_carry_machinery)
    from heat_tpu.config import HeatConfig

    cfg = HeatConfig(n=2048, ntime=steps, dtype="float32", backend="sharded",
                     mesh_shape=(2, 2), fuse_steps=4, local_kernel="pallas")
    _, advance, _ = make_padded_carry_machinery(cfg, mesh_2x2)
    struct = _probe_state_struct(cfg, mesh_2x2, 4, padded=True)
    hlo = advance.lower(struct, steps).compile().as_text()
    comps = _computations(hlo)
    whole = ("f32[1032,1032]", "f32[1280,1152]")   # logical shard, carry
    bodies = re.findall(r"while\(.*?body=%([\w.\-]+)", hlo)
    assert bodies, "no fused loop in the program"
    for body in bodies:
        text = comps[body]
        assert " pad(" not in text, body
        assert not [op for s in whole for op in _ops(text, "slice", s)], body
        assert not [op for s in whole for op in _ops(text, "copy", s)], body
        kernels = re.findall(r"%heat_ftcs_2d\S* = .* custom-call\(", text)
        assert len(kernels) == 2, body
        cuts = re.findall(r"%heat_halo_cut\S* = .* custom-call\(", text)
        assert len(cuts) == 2, body
    copies = [op for s in whole for op in _ops(hlo, "copy", s)]
    assert len(copies) <= 1, copies
    assert not [op for s in whole for op in _ops(hlo, "pad", s)]


def test_2d_plan_at_flagship_32768_f32(one_chip):
    """configs/input_all.dat's grid: the plan _plan_2d picks there, and a
    4 GiB field that must still fit with the kernel's buffers."""
    from heat_tpu.ops.pallas_stencil import _plan_2d

    assert _plan_2d((32768, 32768), "float32", 2)[0] == "thin"
    total = _check(_solo(one_chip, 32768, 2))
    assert total >= 4 * 2**30


def test_2d_serve_lane_kernel_bucket_1024(one_chip):
    """The serving engine's multi-lane Pallas program at the largest
    default bucket, 4 lanes (the default lane budget)."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.ops.pallas_stencil import lane_state_shape
    from heat_tpu.serve.engine import BucketKey, make_lane_advance

    lanes, k = 4, 4
    key = BucketKey(ndim=2, n=1024, dtype="float32", bc="edges")
    shape = (lanes,) + lane_state_shape(2, 1024, "float32")

    def spec(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    advance = make_lane_advance(key, kernel="pallas")
    compiled = advance.lower(spec(shape, jnp.float32),
                             spec((lanes,), jnp.float32),
                             spec((lanes,), jnp.int32),
                             spec((lanes,), jnp.int32), k).compile()
    _check(compiled)


def _bench_util():
    """benchmarks/_util.py, where the compile labs keep topology_spec."""
    import sys
    from pathlib import Path

    bench_dir = str(Path(__file__).resolve().parent.parent / "benchmarks")
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    import _util

    return _util


def test_topology_spec_mapping():
    topology_spec = _bench_util().topology_spec

    # single-chip (the BENCH path) needs the sub-host bounds override:
    # the default chips_per_host_bounds 2x2x1 rejects "v5e:1x1" as not
    # divisible (observed on the installed libtpu)
    assert topology_spec("v5e", 1) == (
        "v5e:1x1", {"chips_per_host_bounds": [1, 1, 1]})
    # full-host layouts use the default bounds
    assert topology_spec("v5e", 4) == ("v5e:2x2", {})
    assert topology_spec("v6e", 16) == ("v6e:4x4", {})
    # v5p/v4 are 3-D spellings ("v5p:2x4" was never valid)
    assert topology_spec("v5p", 8) == ("v5p:2x2x2", {})
    assert topology_spec("v5p", 1) == (
        "v5p:1x1x1", {"chips_per_host_bounds": [1, 1, 1]})
    # v4 exposes two devices per chip -> odd counts unspellable
    assert topology_spec("v4", 2) == (
        "v4:1x1x1", {"chips_per_host_bounds": [1, 1, 1]})
    assert topology_spec("v4", 1) is None
    assert topology_spec("v5e", 3) is None  # no spelling
    assert topology_spec("unknown-chip", 4) is None


def test_topology_specs_construct():
    """Every spelled topology must actually CONSTRUCT against libtpu —
    the flat-table bug shipped precisely because the spellings were
    never validated (the old test pinned two invalid ones). Chipless:
    get_topology_desc needs only the libtpu compiler, no device."""
    pytest.importorskip("jax.experimental.topologies")
    from jax.experimental import topologies

    util = _bench_util()
    _TOPO_BY_CHIP, topology_spec = util._TOPO_BY_CHIP, util.topology_spec

    try:
        topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception:
        pytest.skip("no TPU-capable libtpu on this host")

    for chip, table in _TOPO_BY_CHIP.items():
        for ndev in table:
            name, kwargs = topology_spec(chip, ndev)
            topo = topologies.get_topology_desc(name, "tpu", **kwargs)
            assert len(topo.devices) == ndev, (
                f"{name} {kwargs}: {len(topo.devices)} devices, "
                f"expected {ndev}")
