"""Share of the batches the window's scans yielded that were replayed
from the device scan cache: 100 x ``scan.batches.cached`` /
(``scan.batches.cached`` + ``scan.batches.read``) over the window's
query numbers (``obs.trace.coarse_counts()``).  Under 100 the window
timed Parquet decode and upload.  Nothing when neither was counted (an
engine without the ``scan.*`` counters)."""
import span_reduce


def read(run):
    cached = span_reduce.counts_per_query(run, "scan.batches.cached")
    decoded = span_reduce.counts_per_query(run, "scan.batches.read")
    if not cached and not decoded:
        return None
    return 100.0 * cached / (cached + decoded)
