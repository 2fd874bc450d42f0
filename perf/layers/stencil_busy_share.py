"""stencil_busy_share: device time in the stencil kernels' events over the
device's busy time (``trace.busy_ns``), in %, averaged over the devices.
A stencil kernel is a device event named for one of ``ops/pallas_stencil.py``'s
``heat_ftcs_*`` or ``heat_lane_*`` kernels (their ``pallas_call`` names).
None where no device event carries such a name."""

import re

from perf import trace

_NAMED = re.compile(r"\bheat_(ftcs|lane)_")


def read(run):
    shares = []
    for d in trace.devices(run):
        named = sum(e - s for n, s, e in trace.op_events(run, d)
                    if _NAMED.search(n))
        busy = trace.busy_ns(run, d)
        if named > 0 and busy > 0:
            shares.append(named / busy)
    return 100.0 * sum(shares) / len(shares) if shares else None
