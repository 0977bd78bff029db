"""Host time inside ``next(batches)`` (the bench:next-batch span) over
the window."""


def read(run):
    job = run["job"]
    return 100.0 * job["data_wait_s"] / job["window_s"]
