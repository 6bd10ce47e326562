"""Mean wall ms of the planner path (``session.last_query_plan_cache[1]``:
parse, analyse, plan-cache look-up or plan) over the window's queries."""


def read(run):
    v = [r["planner_ms"] for r in run["queries"]
         if r.get("planner_ms") is not None]
    return sum(v) / len(v) if v else None
