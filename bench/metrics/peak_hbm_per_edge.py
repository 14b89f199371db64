"""Bytes of device memory at the process's peak
(``memory_stats()["peak_bytes_in_use"]`` on the fullest chip, read after
the window and before the reference) per directed edge slot: what bounds
the largest graph one chip holds."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / run.edge_slots
