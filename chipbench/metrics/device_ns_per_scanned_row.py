"""Device busy time of the traced pass over the rows its scans yielded
(the ``scan.rows`` counter of the traced queries' numbers): what a row
costs the device from scan to answer, the same figure at any scale.
Nothing without a device trace or without the counter."""
import span_reduce


def read(run):
    t = run.get("trace")
    w = span_reduce.window(run)
    if not t or not t.get("queries") or not t.get("busy_s") or w is None:
        return None
    # the traced pass is the window's first: its records lead the list
    traced = span_reduce.query_intervals(run)[:len(t["queries"])]
    numbers = {s["query"] for s in span_reduce.in_window(w["spans"], traced)
               if s["name"] == "srt.query"}
    rows = sum(w["counts"].get(q, {}).get("scan.rows", 0) for q in numbers)
    return t["busy_s"] * 1e9 / rows if rows else None
