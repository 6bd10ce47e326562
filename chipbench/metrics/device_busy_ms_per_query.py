"""Device busy time (union of the device-op intervals of the traced
pass) per query of that pass."""


def read(run):
    t = run["trace"]
    if not t or not t["queries"]:
        return None
    return t["busy_s"] * 1e3 / len(t["queries"])
