"""Shared helpers for the benchmark scripts."""

from __future__ import annotations

import json
import os
from pathlib import Path


def write_atomic(out: Path, obj) -> None:
    """Temp-file + rename: a SIGKILL mid-write (row/phase timeout,
    external deadline) must not leave truncated JSON that poisons later
    merges or re-reads."""
    tmp = out.with_suffix(".tmp")
    tmp.write_text(json.dumps(obj, indent=2))
    os.replace(tmp, out)


def ensure_cache_env() -> str:
    """``heat_tpu.utils.cache.ensure_cache_env`` for supervisor processes
    that must stay jax-free (compile_bisect, run_all parents: importing
    the heat_tpu package pulls jax in, adding import cost and failure
    modes to the process whose job is to outlive wedged children). The
    module file is stdlib-only, so load it by PATH — one source of truth
    for the cache-dir derivation, no package ``__init__`` executed."""
    import importlib.util

    src = Path(__file__).parent.parent / "heat_tpu" / "utils" / "cache.py"
    spec = importlib.util.spec_from_file_location("_heat_cache_standalone",
                                                  src)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.ensure_cache_env()


def deep_fuse_proven(k: int = 32, budget_s: float = 1500) -> bool:
    """Has a bisect artifact PROVEN the depth-``k`` flagship compile
    bounded? True once either the on-chip bisect or the chipless
    AOT-topology bisect recorded a sub-budget compile of the REAL
    (Pallas local kernel) program. Round-4 measured truth
    (compile_bisect_topology.json, local_kernel pinned to pallas):
    16384-local k=8/16/32 cold-compile in 393/980/665 s — minutes,
    bounded, inside the 1500 s default — while the 8192-local thin-band
    k=32 family is a genuine >36-min wedge. The gate the chip lab
    (collective_overhead) consults before queueing deep-fuse rows."""
    here = Path(__file__).parent
    for fname in ("compile_bisect.json", "compile_bisect_topology.json"):
        try:
            rows = json.loads((here / fname).read_text())["rows"]
            row = rows.get(str(k), {})
            # rows must prove the PALLAS program: the retracted first
            # curves measured the XLA path (local_kernel unpinned) and
            # rows from that era carry no local_kernel field — reject them
            if (row.get("local_kernel") == "pallas"
                    and "compile_s" in row and row["compile_s"] < budget_s):
                return True
        except (OSError, json.JSONDecodeError, KeyError):
            continue
    return False


def custom_call_census(txt: str, call_marker: str, target_re: str) -> dict:
    """Census of custom calls in a compiler-IR text dump: total calls,
    Mosaic (TPU) calls, and distinct payloads after SSA-id normalization.

    ONE implementation for both the post-compile HLO census
    (compile_bisect: ``call_marker="custom-call"``) and the lowering-IR
    census (kernel_census: ``"stablehlo.custom_call"``) — the first cut
    existed twice and one copy silently recorded zeros when the printer
    syntax didn't match its regex (the round-5 k=8/16 bisect rows).
    When the target regex matches nothing but call lines exist, falls
    back to whole-line hashing and SAYS so (``census_method``) instead of
    recording a confident zero."""
    import hashlib
    import re

    lines = [ln for ln in txt.splitlines() if call_marker in ln]
    mosaic, method, matched_any = [], "target-match", False
    for ln in lines:
        m = re.search(target_re, ln)
        if m:
            matched_any = True
            if "tpu" in m.group(1):
                mosaic.append(m.group(0))
    if lines and not matched_any:
        # printer-syntax mismatch (NO line parsed): count via line
        # hashing and say so. A parse that succeeds but finds zero TPU
        # targets is a real mosaic_calls=0 (e.g. an xla-local-kernel
        # program with only host/sharding custom calls) — not a fallback.
        mosaic, method = list(lines), "line-hash-fallback"
    norm = [re.sub(r"%[\w#.\-]+", "%", c) for c in mosaic]
    return {"custom_calls": len(lines),
            "mosaic_calls": len(mosaic),
            "distinct_kernel_bodies": len(
                {hashlib.sha1(c.encode()).hexdigest() for c in norm}),
            "census_method": method}


# Device counts the chipless compile labs know how to spell as a physical
# topology for jax.experimental.topologies.get_topology_desc. The map is
# PER CHIP, not a flat ndev->layout table, because (validated against
# the installed libtpu, round 5):
#   * v5e/v6e topologies are 2-D, v4/v5p are 3-D — "v5p:2x4" is an
#     invalid spelling, the old flat table's entry never worked;
#   * the default chips_per_host_bounds is 2x2x1, so any sub-host
#     layout ("v5e:1x1" — the single-chip BENCH path) is rejected as
#     "not divisible" unless the bounds are overridden. The override
#     must be a plain int list: the PJRT option is typed, and a string
#     form fails with INVALID_ARGUMENT;
#   * v4 exposes TWO TensorCore devices per chip here (1x1x1 -> 2
#     devices), so its entries are keyed by even device counts only.
def _subhost(*bounds: int) -> dict:
    return {"chips_per_host_bounds": list(bounds)}


# v6e aliases the v5e table (same 2-D spellings and host bounds) so a
# future spelling fix cannot drift between them
_V5E_LIKE = {1: ("1x1", _subhost(1, 1, 1)), 2: ("1x2", _subhost(1, 2, 1)),
             4: ("2x2", {}), 8: ("2x4", {}), 16: ("4x4", {})}

_TOPO_BY_CHIP: dict[str, dict[int, tuple[str, dict]]] = {
    "v5e": _V5E_LIKE,
    "v6e": _V5E_LIKE,
    "v5p": {1: ("1x1x1", _subhost(1, 1, 1)), 2: ("1x2x1", _subhost(1, 2, 1)),
            4: ("2x2x1", {}), 8: ("2x2x2", {}), 16: ("2x2x4", {})},
    "v4":  {2: ("1x1x1", _subhost(1, 1, 1)), 4: ("1x2x1", _subhost(1, 2, 1)),
            8: ("2x2x1", {}), 16: ("2x2x2", {})},
}


def topology_spec(chip: str, ndev: int) -> tuple[str, dict] | None:
    """(topology_name, get_topology_desc kwargs) for ``ndev`` devices on
    ``chip``, or None when there is no spelling."""
    entry = _TOPO_BY_CHIP.get(chip, {}).get(ndev)
    if entry is None:
        return None
    name, kwargs = entry
    return f"{chip}:{name}", kwargs
