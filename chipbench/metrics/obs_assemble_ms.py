"""Mean host ms per query that ``collect()`` spends assembling its
observability records after the answer is ready (span
``srt.obs.assemble``: stats profile, doctor, fingerprint and history
deposit, event-log record), read through ``span_reduce.py``."""
import span_reduce


def read(run):
    return span_reduce.layer_ms(run, ("srt.obs.assemble",), self_time=False)
