"""kernel_useful_share: ``drive``'s useful point-steps over the
point-steps its kernels compute, in %: the program's own counters
``heat.drive.points_useful`` (n^ndim x steps) and
``heat.kernel.points_computed`` (each kernel pass's band, halo rows and
alignment padding included), read from the process's tracers after the
window (``runtime/trace.py::counts_total``). Every chunk of a cell runs
the same programs, so set-up's solves leave the ratio as it is. None
where the program keeps no such counters, or the trace holds no
``heat.drive.dispatch`` span."""

from perf import counters

USEFUL, COMPUTED = "heat.drive.points_useful", "heat.kernel.points_computed"


def read(run):
    return counters.ratio(run, USEFUL, COMPUTED, "heat.drive.dispatch")
