"""Share of the launched pixels that answered no request over the window:
100 x (1 - pixels of the requests answered / batch slots or tiles times
their bucket or tile extent), from the service's ``executor.pixels_valid``
and ``executor.pixels_launched`` counters, as differences."""


def read(run):
    launched = run.counter_delta("executor.pixels_launched")
    if launched <= 0:
        return None
    return 100.0 * (1.0 - run.counter_delta("executor.pixels_valid") / launched)
