"""Peak device memory in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GiB."""


def read(run):
    return run.peak_bytes / 2**30 if run.peak_bytes else None
