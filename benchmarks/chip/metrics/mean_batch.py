"""Requests per batched dispatch over the window: the service's own counters
(requests less tiled and run-length routed, over batches), as differences."""


def read(run):
    batches = run.counter_delta("batches")
    if batches <= 0:
        return None
    batched = (run.counter_delta("requests") - run.counter_delta("tiled_requests")
               - run.counter_delta("rle_requests"))
    return batched / batches
