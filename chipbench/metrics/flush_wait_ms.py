"""Mean host ms per query blocked on the device or on the copy back:
self time of ``srt.flush`` (a pending-pool flush) and ``srt.pull`` (every
declared device-to-host transfer region, the one inside a flush and the
result's among them; nested ones count once)."""
import span_reduce


def read(run):
    return span_reduce.layer_ms(run, ("srt.flush", "srt.pull"))
