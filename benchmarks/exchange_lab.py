"""Where does the per-exchange cost go? (round 3)

``collective_overhead.py``'s exchange_delta probe measured C ~= 9.1 ms
per width-k exchange at 16384^2 f32 on the 1x1 mesh (fit over fuse
k={1,8}). Accounting: one kernel HBM pass ~2.6 ms + 4 ppermute
dispatches ~1.2 ms (probe 1) leaves ~5 ms unexplained — about two full
passes of the 1 GiB padded array, i.e. the ghost-write
``out.at[slab].set(...)`` updates in ``parallel/halo.py:111-112``
plausibly materialize full-array copies instead of in-place
dynamic-update-slices.

This lab times the *exchange alone* (jit'd, two-point protocol) in
three formulations and dumps the compiled HLO op census so the copies
are visible, not inferred:

- ``dus``     the shipped halo_exchange (4 sequential .at.set writes)
- ``concat``  rebuild each axis by concatenate([ghost, interior, ghost])
              (one explicit full pass per axis, no DUS aliasing question)
- ``donate``  the shipped exchange under jit with the padded buffer
              donated (gives XLA permission to update in place)

Run on chip: ``python benchmarks/exchange_lab.py [n]``; CPU smoke:
``python benchmarks/exchange_lab.py --smoke``. Writes
benchmarks/exchange_lab.json (atomic, incremental).

Findings so far (CPU census, 4x2 virtual mesh): the sequential exchange
cost the compiled advance 3 copies/iteration (2 full-local-shard); the
indep exchange, now the only one, removes one full-shape copy. The remaining
full-shard copy is NOT exchange-related — a control with a pure
stencil loop body (no exchange at all) shows the identical census, so
it belongs to the fori_loop carry structure itself and no exchange
reformulation can remove it. Python-unrolling the fused-block loop was
tried and REJECTED: a pure elementwise body unrolls to zero copies, but
the real exchange+kernel body keeps one copy per unrolled block
(executed-copy count unchanged from the while form), so the unroll only
buys bigger programs. CPU censuses also understate the TPU picture —
off-TPU the pallas kernel runs as inlined interpret HLO, not a Mosaic
custom call — which is why the on-chip census rows below exist.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from _util import write_atomic  # noqa: E402


def _census(compiled) -> dict:
    """Count the ops that matter in the compiled HLO: full-array copies
    and fusions (a DUS inside a fusion is in-place; a standalone copy op
    is the smoking gun). copy_shapes says whether each copy is the full
    padded array or a cheap slab."""
    import re

    txt = compiled.as_text()
    copy_shapes = re.findall(r"=\s*(\S+?)\{[^}]*\}?\S*\s+copy\(", txt)
    return {
        "copy": txt.count(" copy("),
        "copy_shapes": copy_shapes[:8],
        "dynamic-update-slice": txt.count("dynamic-update-slice"),
        "fusion": txt.count(" fusion("),
        "collective-permute": txt.count("collective-permute"),
        "all-to-all": txt.count("all-to-all"),
    }


# NOTE: *schedule* evidence (kernels inside the async
# collective-permute flight window) is NOT measurable here — this lab's
# real-advance rows run the 1x1 mesh, where ppermute degenerates and the
# compiled module has zero collective-permute pairs. The multi-chip
# schedule census lives in benchmarks/topology_schedule.py (AOT topology
# compile — works without any attached chip).


def variants(axis_names, axis_sizes, bc_value, w):
    import jax
    import jax.numpy as jnp

    from heat_tpu.parallel.halo import halo_exchange

    def dus(padded):
        return halo_exchange(padded, axis_names, axis_sizes, bc_value,
                             width=w)

    def concat(padded):
        from jax import lax

        nd = padded.ndim
        bc = jnp.asarray(bc_value, padded.dtype)
        out = padded
        for d, (name, size) in enumerate(zip(axis_names, axis_sizes)):
            idx = lax.axis_index(name)

            def slab(sl_d):
                sl = [slice(None)] * nd
                sl[d] = sl_d
                return tuple(sl)

            send_lo = out[slab(slice(w, 2 * w))]
            send_hi = out[slab(slice(-2 * w, -w))]
            pairs_fwd = [(i, i + 1) for i in range(size - 1)]
            pairs_bwd = [(i + 1, i) for i in range(size - 1)]
            from_prev = lax.ppermute(send_hi, name, pairs_fwd)
            from_next = lax.ppermute(send_lo, name, pairs_bwd)
            from_prev = jnp.where(idx == 0, bc, from_prev)
            from_next = jnp.where(idx == size - 1, bc, from_next)
            out = jnp.concatenate(
                [from_prev, out[slab(slice(w, -w))], from_next], axis=d)
        return out

    return {"dus": dus, "concat": concat}


def main():
    smoke = "--smoke" in sys.argv
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    if smoke:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp

    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    from heat_tpu.runtime.timing import two_point_rate

    n = int(args[0]) if args else (512 if smoke else 16384)
    w = 8
    mesh = Mesh(jax.devices()[:1], ("x",))
    axis_names, axis_sizes = ("x",), (1,)
    padded = jnp.zeros((n + 2 * w, n + 2 * w), jnp.float32)

    out = Path(__file__).parent / (
        "exchange_lab_smoke.json" if smoke else "exchange_lab.json")
    rec = {"ts": time.time(), "platform": jax.default_backend(),
           "n": n, "w": w, "variants": {}}

    fns = variants(axis_names, axis_sizes, 2.0, w)
    for name, fn in fns.items():
        for donate in ((False, True) if name == "dus" else (False,)):
            label = "donate" if donate else name
            sm = shard_map(fn, mesh=mesh, in_specs=(P("x"),),
                           out_specs=P("x"))
            jf = (jax.jit(sm, donate_argnums=0) if donate
                  else jax.jit(sm))
            lowered = jf.lower(jax.ShapeDtypeStruct(padded.shape,
                                                    padded.dtype))
            compiled = lowered.compile()
            census = _census(compiled)
            # two_point_rate recycles the output as the next input, so a
            # donating executable just cycles one buffer pair
            # time the AOT executable itself — calling jf would re-trace
            # and re-compile a second copy of each large program
            rate, _ = two_point_rate(compiled, jnp.zeros_like(padded),
                                     padded.size, repeats=3)
            per_call_s = padded.size / rate if rate else None
            rec["variants"][label] = {"hlo": census,
                                      "per_exchange_s": per_call_s}
            per_call_us = (f"{per_call_s * 1e6:9.1f} us"
                           if per_call_s is not None else "      n/a")
            print(f"{label:8s} per-exchange {per_call_us}  "
                  f"hlo={census}", flush=True)
            write_atomic(out, rec)

    # the real thing: HLO census of the shipped padded-carry advance (the
    # program collective_overhead's exchange_delta times) — copies here
    # are copies the solve actually pays, donation and all
    from heat_tpu.backends.sharded import make_padded_carry_machinery
    from heat_tpu.config import HeatConfig

    from heat_tpu.parallel.mesh import build_mesh

    steps = 64
    for kf in (1, 8):
        cfg = HeatConfig(n=n, ntime=steps, dtype="float32",
                         backend="sharded", mesh_shape=(1, 1), fuse_steps=kf)
        hmesh = build_mesh(cfg.ndim, cfg.mesh_shape)
        seed, advance, crop = make_padded_carry_machinery(cfg, hmesh)
        Tp = seed(jnp.zeros((n, n), jnp.float32))
        compiled = advance.lower(Tp, steps).compile()
        census = _census(compiled)
        # the advance donates its carry, so two_point recycles buffers
        # static step-count arg is baked into the executable; Tp is
        # donated into the measurement (lowering didn't consume it) —
        # a second seeded buffer would double resident padded state
        rate, _ = two_point_rate(compiled, Tp, n * n * steps, repeats=3)
        del Tp
        per_step = n * n / rate if rate else None
        # keyed by the exchange's name, as in the older records
        rec["variants"][f"real_advance_indep_fuse{kf}"] = {
            "hlo": census, "per_step_s": per_step}
        per_step_us = (f"{per_step * 1e6:9.1f} us"
                       if per_step is not None else "      n/a")
        print(f"real advance fuse={kf}: per-step {per_step_us}  "
              f"hlo={census}", flush=True)
        write_atomic(out, rec)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
