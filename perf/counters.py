"""The program's own counters (``heat_tpu/runtime/trace.py``: ``COUNTERS``,
summed over the process's tracers by ``counts_total``), read in the
process that ran the cell, after the window. A reading belongs to a run
whose trace holds the span of the dispatches that counted."""

from __future__ import annotations


def totals() -> dict:
    """The counters' totals, or ``{}`` where the program keeps none."""
    from heat_tpu.runtime import trace as program_trace

    read = getattr(program_trace, "counts_total", None)
    return read() if read is not None else {}


def ratio(run, num: str, den: str, span: str):
    """``num`` over ``den`` in %; None unless the run's host events hold
    ``span`` and the denominator is counted."""
    host = (run.events or {}).get("host", ())
    if not any(n == span for _, n, _, _ in host):
        return None
    c = totals()
    if num not in c or not c.get(den):
        return None
    return 100.0 * c[num] / c[den]
