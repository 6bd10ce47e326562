"""Backend compiles inside the measured window (JAX monitoring events);
a warmed-up engine reads 0, and 0 is a reading."""


def read(run):
    return run["compile"]["window"]["backend_compiles"]
