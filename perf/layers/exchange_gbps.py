"""exchange_gbps: the rate at which the halo exchange moves its bytes, in
GB/s: for each device, the bytes the window's exchanges sent over that
device's collective-permute time (``trace.collective_events``, their union),
averaged over the devices.

The bytes come from the program's counters: ``heat.halo.bytes`` (what one
device sends, mean of the devices) over the solves counted, which are
``heat.drive.points_useful`` / (n^2 x ntime) and include set-up's warm
solve, times the window's solves. None where the program keeps no such
counters, or the trace holds no collective events."""

from perf import counters, trace


def read(run):
    solves = [r for r in run.records if "points" in r]
    c = counters.totals()
    sent, useful = c.get("heat.halo.bytes"), c.get("heat.drive.points_useful")
    if not solves or not sent or not useful:
        return None
    counted = useful / (run.cfg["n"] ** 2 * run.cfg["ntime"])
    window_bytes = sent / counted * len(solves)
    rates = []
    for d in trace.devices(run):
        ns = trace.union_ns(trace.collective_events(run, d))
        if ns > 0:
            rates.append(window_bytes / ns)      # bytes per ns = GB/s
    return sum(rates) / len(rates) if rates else None
