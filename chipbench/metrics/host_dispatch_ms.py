"""Mean host ms per query of python that builds and enqueues device
programs: self time of the ``srt.exec.<Node>`` spans, all threads.
Flush, pull and jit build are child spans and do not count."""
import span_reduce


def read(run):
    return span_reduce.layer_ms(run, ("srt.exec.",))
