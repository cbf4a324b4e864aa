"""Images answered per second: every answer that arrived inside the window,
over the window's length (host clock)."""


def read(run):
    return len(run.completed_in_window()) / (run.t1 - run.t0)
