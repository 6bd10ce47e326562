"""``peak_bytes_in_use`` of the fullest chip after the window."""


def read(run):
    b = run["memory_peak_bytes"]
    return b / 1e9 if b else None
