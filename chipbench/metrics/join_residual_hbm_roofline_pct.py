"""The least time the chip could take over the residual join's
candidate pairs, over the device time its programs took.

Least time: ``bytes_per_query`` at the ``peaks.json`` HBM bandwidth.
The bytes are the engine's ``join.residual.bytes`` counter, which adds,
from dtypes and pair counts alone, for every candidate pair the bytes
of the condition's input columns on both sides (data and validity), the
pair's two int32 gather maps and its survivor flag: each byte the
program must touch once, whatever it does with it (the expansion's
running sums, the random gathers and the compaction are the engine's
choice and are not counted).  Nothing without the counter or without a
``jit_join_residual*`` program among the traced pass's ten longest."""
import span_reduce


def bytes_per_query(run):
    return span_reduce.counts_per_query(run, "join.residual.bytes")


def read(run):
    need = bytes_per_query(run)
    ms = span_reduce.device_ms_per_query(run, "jit_join_residual")
    if not need or not ms or not run["peaks"]:
        return None
    least_ms = need / (run["peaks"]["hbm_gbps"] * 1e9) * 1e3
    return 100.0 * least_ms / ms
