"""Backend compile seconds during set-up (JAX monitoring events): the
part of ``setup_s`` that the persistent cache did not take."""


def read(run):
    return run["compile"]["setup"]["backend_compile_s"]
