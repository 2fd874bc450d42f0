"""padding_useful_share: the lane engine's useful point-steps over the
point-steps its chunks compute, in %: the program's counters
``heat.lane.points_useful`` (over the live lanes of each chunk, n^ndim x
the steps each still takes) and ``heat.lane.points_computed`` (lanes x
lane slab points x steps: bucket padding, margins and idle lanes
included). None where the program keeps no such counters, or the trace
holds no ``heat.sched.dispatch`` span."""

from perf import counters


def read(run):
    return counters.ratio(run, "heat.lane.points_useful",
                          "heat.lane.points_computed",
                          "heat.sched.dispatch")
