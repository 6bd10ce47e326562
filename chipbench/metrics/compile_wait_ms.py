"""Mean host ms per query spent compiling or loading a program from the
persistent cache inside the window: the whole duration of the
``srt.compile`` spans (``obs/compile_watch.py``'s listener on jax's
backend-compile event, args ``program`` and ``how``).  0 is a reading;
nothing from an engine whose spans carry no ``cpu_ns`` (before the
listener)."""
import span_reduce


def read(run):
    w = span_reduce.window(run)
    if w is None or not any("cpu_ns" in s for s in w["spans"]):
        return None
    return span_reduce.layer_ms(run, ("srt.compile",), self_time=False)
