"""Real tiles per tiled launch over the window, from the service's
``tiled.tiles`` and ``tiled.launches`` counters, as differences (the rest
of each launch is dummy tiles)."""


def read(run):
    launches = run.counter_delta("tiled.launches")
    if launches <= 0:
        return None
    return run.counter_delta("tiled.tiles") / launches
