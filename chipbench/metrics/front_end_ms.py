"""Mean host ms per query in the front end: self time of the coarse
spans ``srt.sql.parse``, ``srt.sql.analyze`` (``session.sql()``) and
``srt.plan`` (the whole of ``plan_with_cache``, shape key and conf
fingerprint included), read through ``span_reduce.py``."""
import span_reduce


def read(run):
    return span_reduce.layer_ms(run, ("srt.sql.", "srt.plan"))
