"""plan_s: seconds of a second, warm plan of the cell's template (the
planner's profiling and relaxation), timed by the benchmark in set-up."""


def read(w):
    return w.plan_s
