"""drive_fetch_idle_share: share of the traced window, in %, in which the
program's ``heat.drive.fetch`` span (``backends/common.py::drive``: the
solve's final device-to-host fetch, a TraceMe on the profiler's host
plane) is open and the device runs no operation (``trace.exposed_ns``
of the spans against ``trace.op_events``), averaged over the devices.
None where the program writes no such span."""

from perf import trace

SPAN = "heat.drive.fetch"


def read(run):
    devs = trace.devices(run)
    spans = [(s, e) for _, n, s, e in (run.events or {}).get("host", ())
             if n == SPAN]
    if not devs or not spans or not run.trace_window_s:
        return None
    win = run.trace_window_s * 1e9
    exposed = [trace.exposed_ns(spans, [(s, e) for _, s, e
                                        in trace.op_events(run, d)])
               for d in devs]
    return 100.0 * sum(exposed) / len(devs) / win
