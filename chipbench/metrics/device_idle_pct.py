"""Share of the traced pass in which no operation ran on the device."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
