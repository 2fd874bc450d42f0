"""lane_occupancy: the lane slots holding a live request over the lane
slots stepped, summed over the chunks the scheduler dispatched, in %: the
program's counters ``heat.lane.live`` and ``heat.lane.slots``. None where
the program keeps no such counters, or the trace holds no
``heat.sched.dispatch`` span."""

from perf import counters


def read(run):
    return counters.ratio(run, "heat.lane.live", "heat.lane.slots",
                          "heat.sched.dispatch")
