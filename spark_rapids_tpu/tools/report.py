"""Per-query report generator — the SQL-UI / profiling-report stand-in.

Joins the structured event log (engine ``query`` records + service
lifecycle lines, both keyed by the stable ``query_id``) and, when given,
the span tracer's Chrome trace JSON, into one readable per-query story:

- the physical plan tree annotated with each operator's attributed time
  and share of the total (the SQL UI's "time in operator" view);
- the retry/spill story: admission, queue wait, each attempt's outcome,
  backoffs, semaphore wait and spill bytes;
- the critical-path spans from the trace (longest exclusive regions);
- with ``--stats``, the runtime stats plane (obs/stats.py): per-member
  device-time shares inside fused superstages, the per-exchange
  partition/skew/distinct table, and dispatch-duration percentiles
  (degrades to a one-line notice on logs without a StatsProfile).

Tolerant of older logs: records missing newer fields (``flushes``,
``stats_profile``, ``sem_wait_ms``...) render with "-" placeholders
rather than failing.

Usage:
  python -m spark_rapids_tpu.tools.report <event_log.jsonl>
      [--query QID] [--trace trace.json] [--html out.html] [--stats]
"""
from __future__ import annotations

import jax as _jax

# host-side CLI: never touch the accelerator backend
_jax.config.update("jax_platforms", "cpu")

import html as _html
import json
import sys
from typing import Dict, List, Optional

from .events import read_event_log

#: lifecycle kinds emitted by the query service, in story order
_LIFECYCLE = ("admitted", "shed", "retry", "watchdog", "cancelled",
              "completed", "failed")


# ---------------------------------------------------------------------------
# event-log join
# ---------------------------------------------------------------------------

def load_query_stories(path: str) -> Dict:
    """{query_id: {"engine": [query records], "service": [lifecycle
    records]}} across the log and its rotation segments, preserving
    file order within each stream."""
    stories: Dict = {}
    for rec in read_event_log(path, events=None, include_rotated=True):
        qid = rec.get("query_id")
        story = stories.setdefault(
            qid, {"engine": [], "service": []})
        if rec.get("event", "query") == "query":
            story["engine"].append(rec)
        else:
            story["service"].append(rec)
    return stories


# ---------------------------------------------------------------------------
# plan tree with time shares
# ---------------------------------------------------------------------------

def plan_time_shares(record: Dict) -> List[Dict]:
    """One row per plan node: {depth, label, time_ms, share} — the
    node_metrics keys are "<preorder-index>:<Name>" in the same order
    the tree string prints, so the join is positional (the
    generate_dot discipline)."""
    nodes = []
    for ln in record.get("physical_plan", "").splitlines():
        depth = (len(ln) - len(ln.lstrip())) // 2
        nodes.append((depth, ln.strip()))
    metrics = record.get("node_metrics", {})
    keys = list(metrics.keys())
    # per-node verifier verdicts (analysis/plan_verify via the event
    # logger): node_index keys the same preorder the tree prints
    pv = record.get("plan_verify")
    by_node: Dict[int, List[str]] = {}
    if pv:
        for v in pv.get("violations", []):
            by_node.setdefault(int(v["node_index"]), []).append(
                f"{v['rule']}: {v['message']}")
    rows = []
    for i, (depth, label) in enumerate(nodes):
        m = metrics.get(keys[i], {}) if i < len(keys) else {}
        t_ns = sum(v for k, v in m.items()
                   if k.endswith("Time") or k.endswith("time"))
        verify = None
        if pv:
            verify = "[!! " + "; ".join(by_node[i]) + "]" \
                if i in by_node else "[ok]"
        rows.append({"depth": depth, "label": label,
                     "time_ms": t_ns / 1e6,
                     "rows": m.get("numOutputRows"),
                     "verify": verify})
    total = sum(r["time_ms"] for r in rows)
    for r in rows:
        r["share"] = (r["time_ms"] / total) if total else 0.0
    return rows


def _format_plan(rows: List[Dict]) -> List[str]:
    out = []
    for r in rows:
        bar = "#" * int(round(r["share"] * 20))
        annot = f"{r['share'] * 100:5.1f}% {r['time_ms']:9.2f}ms"
        if r.get("rows") is not None:
            annot += f"  rows={r['rows']}"
        line = (f"  {annot:<44s} {bar:<20s} "
                f"{'  ' * r['depth']}{r['label']}")
        if r.get("verify"):
            line += f"  {r['verify']}"
        out.append(line)
    return out


# ---------------------------------------------------------------------------
# trace join (critical-path spans)
# ---------------------------------------------------------------------------

def load_trace(path: str) -> List[Dict]:
    with open(path) as f:
        doc = json.load(f)
    return [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]


def critical_spans(events: List[Dict], query_id,
                   top: int = 12) -> List[Dict]:
    """Longest spans attributed to ``query_id`` (or unattributed when
    the trace holds a single query), grouped by (name, cat)."""
    qid = str(query_id)
    mine = [e for e in events
            if str(e.get("args", {}).get("query_id", qid)) == qid]
    agg: Dict = {}
    for e in mine:
        key = (e["name"], e.get("cat", ""))
        a = agg.setdefault(key, {"name": e["name"],
                                 "cat": e.get("cat", ""),
                                 "count": 0, "total_ms": 0.0,
                                 "max_ms": 0.0})
        dur_ms = e.get("dur", 0.0) / 1e3
        a["count"] += 1
        a["total_ms"] += dur_ms
        a["max_ms"] = max(a["max_ms"], dur_ms)
    out = sorted(agg.values(), key=lambda a: -a["total_ms"])[:top]
    for a in out:
        a["total_ms"] = round(a["total_ms"], 3)
        a["max_ms"] = round(a["max_ms"], 3)
    return out


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _service_story(service: List[Dict]) -> List[str]:
    """The retry/spill story in chronological lines."""
    out = []
    for rec in sorted(service, key=lambda r: r.get("ts", 0)):
        kind = rec.get("event")
        if kind == "admitted":
            out.append(f"admitted    tenant={rec.get('tenant')} "
                       f"priority={rec.get('priority')} "
                       f"queue_depth={rec.get('queue_depth')} "
                       f"deadline_ms={rec.get('deadline_ms')}")
        elif kind == "retry":
            out.append(f"retry #{rec.get('attempt')}    "
                       f"reason={rec.get('reason')} "
                       f"backoff_ms={rec.get('backoff_ms')} "
                       f"overlay={rec.get('conf_overlay')}")
        elif kind == "shed":
            line = f"shed        {rec.get('reason')}"
            if rec.get("diag_bundle"):
                line += f"  bundle={rec['diag_bundle']}"
            out.append(line)
        elif kind == "watchdog":
            out.append(f"watchdog    stalled_s={rec.get('stalled_s')}"
                       + (f"  bundle={rec['diag_bundle']}"
                          if rec.get("diag_bundle") else ""))
        elif kind in ("completed", "failed", "cancelled"):
            # wall-clock split: queue wait / execution / the inline
            # compile time hidden inside execution (perf plane)
            line = (
                f"{kind:<11s} attempts={rec.get('attempts')} "
                f"queue_wait_ms={rec.get('queue_wait_ms')} "
                f"execute_ms={rec.get('execute_ms')} "
                f"inline_compile_ms={_fmt(rec.get('inline_compile_ms'))} "
                f"sem_wait_ms={rec.get('sem_wait_ms')} "
                f"spill_bytes={rec.get('spill_bytes')} "
                f"spill_ms={_fmt(rec.get('spill_ms'))} "
                f"unspill_count={_fmt(rec.get('unspill_count'))}"
                + (f" leaked_entries={rec.get('leaked_entries')}"
                   if rec.get("leaked_entries") else "")
                + (f" error={rec.get('error')}"
                   if rec.get("error") else ""))
            if rec.get("diag_bundle"):
                # the incident artifact for this outcome (render it
                # with tools/diagnose.py)
                line += f"  bundle={rec['diag_bundle']}"
            out.append(line)
            pred_ms = rec.get("predicted_exec_ms")
            if pred_ms is not None:
                # admission-time prediction vs what actually happened
                # (service/scheduler.py honesty metric)
                pline = f"predicted   exec_ms={pred_ms}"
                actual = rec.get("execute_ms")
                if kind == "completed" and isinstance(
                        actual, (int, float)) and actual > 0:
                    err = abs(float(pred_ms) - float(actual)) \
                        / float(actual) * 100.0
                    pline += (f" actual_ms={actual} "
                              f"err={err:.1f}%")
                out.append(pline)
    return out


def _fmt(v):
    """Missing-field placeholder: older event logs predate newer record
    fields (flushes, sem_wait_ms, stats_profile) and must still render."""
    return "-" if v is None else v


def util_lines(rec: Dict) -> List[str]:
    """The device-utilization lane of one engine record: busy share of
    the query window plus the idle-gap attribution breakdown
    (obs/timeline.py gap taxonomy)."""
    util = rec.get("device_util_pct")
    if util is None:
        return []
    lines = ["-- device utilization --"]
    bar = "#" * int(round(util / 5.0))
    lines.append(f"  busy {util:6.1f}%  {bar:<20s} "
                 f"busy_ms={_fmt(rec.get('device_busy_ms'))}")
    gaps = rec.get("util_gap_breakdown") or {}
    for cause, pct in sorted(gaps.items(), key=lambda kv: -kv[1]):
        if pct > 0:
            bar = "." * int(round(pct / 5.0))
            lines.append(f"  {cause:<21s}{pct:6.1f}%  {bar}")
    return lines


def obs_lines(rec: Dict) -> List[str]:
    """The observability self-cost line of one engine record: host ms
    the default-on planes billed to THEMSELVES inside this query's
    window, with the per-plane split (obs/overhead.py self-meter).
    Pre-r17 logs carry no ``obs_self`` key and render nothing — the
    same tolerance convention as the other per-plane sections."""
    obs = rec.get("obs_self")
    if not obs:
        return []
    planes = obs.get("planes") or {}
    split = " ".join(f"{k}={_fmt(planes.get(k))}" for k in planes)
    return ["-- observability self-cost (obs tax) --",
            f"  obs_self_ms={_fmt(obs.get('total_ms'))}  {split}"]


def compile_lines(rec: Dict) -> List[str]:
    """The compile story of one engine record: every compile that
    landed in the query's window, slowest first — the same dur_ms the
    tpu_compile_seconds histogram observed."""
    compiles = rec.get("compiles") or []
    if not compiles:
        return []
    lines = ["-- compiles in query window --"]
    lines.append(f"  {'cache':<22s}{'dur_ms':>10s}  {'origin':<11s}"
                 f"{'bucket':>8s}  signature")
    for c in sorted(compiles, key=lambda c: -(c.get("dur_ms") or 0)):
        # AOT dimensions (compile/aot.py); pre-r13 records carry
        # neither key — inline flag maps to origin, bucket renders "-"
        origin = c.get("origin") or (
            "inline" if c.get("inline") else "warm")
        bucket = c.get("bucket")
        lines.append(f"  {str(c.get('cache')):<22s}"
                     f"{_fmt(c.get('dur_ms')):>10}  "
                     f"{str(origin):<11s}"
                     f"{('-' if bucket is None else str(bucket)):>8s}  "
                     f"{str(c.get('signature', ''))[:60]}")
    return lines


def shuffle_lines(rec: Dict) -> List[str]:
    """The shuffle-transport (netplane) section of one engine record:
    the four-phase host-drop split (summing to the exchange wall by
    construction), the per-edge heat table and the per-peer fetch
    latency aggregate — obs/netplane.py's event-log surface."""
    net = rec.get("shuffle_netplane")
    if not net:
        return ["  (no shuffle netplane recorded — older log or "
                "spark.rapids.tpu.obs.net.enabled=false)"]
    lines = ["-- shuffle transport (netplane) --"]
    lines.append(
        f"  host_drop_tax_ms={_fmt(net.get('host_drop_tax_ms'))} "
        f"exchange_wall_ms={_fmt(net.get('exchange_wall_ms'))} "
        f"wire_MBps={_fmt(net.get('wire_MBps'))} "
        f"edge_skew={_fmt(net.get('edge_skew'))} "
        f"edges={_fmt(net.get('edges'))} "
        f"blocks={_fmt(net.get('blocks'))}")
    phases = net.get("phases_ms") or {}
    wall = float(net.get("exchange_wall_ms") or 0.0)
    for phase in ("serialize", "dwell", "wire", "deserialize"):
        ms = phases.get(phase)
        if ms is None:
            continue
        share = (ms / wall * 100.0) if wall else 0.0
        bar = "#" * int(round(share / 5.0))
        lines.append(f"  {phase:<13s}{share:6.1f}%{ms:>12.3f}ms  {bar}")
    comp = net.get("compression") or {}
    if comp.get("raw_bytes"):
        codecs = ",".join(comp.get("codecs") or []) or "-"
        lines.append(
            f"  compression [{codecs}]: "
            f"raw={_fmt(comp.get('raw_bytes'))} "
            f"compressed={_fmt(comp.get('compressed_bytes'))} "
            f"ratio={_fmt(comp.get('ratio'))}x")
    edges = net.get("top_edges") or []
    if edges:
        lines.append("  top edges (map -> reduce):")
        lines.append(f"    {'shuffle':>7s}{'map':>6s}{'reduce':>8s}"
                     f"{'rows':>10s}{'bytes':>12s}{'batches':>9s}")
        for e in edges:
            lines.append(f"    {_fmt(e.get('shuffle_id')):>7}"
                         f"{_fmt(e.get('map_id')):>6}"
                         f"{_fmt(e.get('reduce_id')):>8}"
                         f"{_fmt(e.get('rows')):>10}"
                         f"{_fmt(e.get('bytes')):>12}"
                         f"{_fmt(e.get('batches')):>9}")
    peers = net.get("fetch_peers") or {}
    if peers:
        lines.append("  per-peer fetch latency:")
        lines.append(f"    {'peer':<18s}{'count':>6s}{'avg_ms':>10s}"
                     f"{'max_ms':>10s}{'bytes':>12s}")
        for peer in sorted(peers):
            p = peers[peer]
            lines.append(f"    {peer:<18s}{_fmt(p.get('count')):>6}"
                         f"{_fmt(p.get('avg_ms')):>10}"
                         f"{_fmt(p.get('max_ms')):>10}"
                         f"{_fmt(p.get('bytes')):>12}")
    return lines


def memory_lines(rec: Dict) -> List[str]:
    """The HBM memory (memplane) section of one engine record: peak
    device bytes with the owner set at peak time, the per-direction
    spill totals, the priced ledger tail and any retention leaks —
    obs/memplane.py's event-log surface."""
    mem = rec.get("memplane")
    if not mem:
        return ["  (no memplane recorded — older log or "
                "spark.rapids.tpu.obs.mem.enabled=false)"]
    lines = ["-- HBM memory (memplane) --"]
    lines.append(
        f"  peak_device_bytes={_fmt(mem.get('peak_device_bytes'))} "
        f"spill_ms={_fmt(mem.get('spill_ms'))} "
        f"unspill_ms={_fmt(mem.get('unspill_ms'))} "
        f"unspill_count={_fmt(mem.get('unspill_count'))} "
        f"spill_skipped={_fmt(mem.get('spill_skipped'))} "
        f"leaked_entries={_fmt(mem.get('leaked_entries'))}")
    peak_sites = mem.get("peak_by_site") or {}
    peak = float(mem.get("peak_device_bytes") or 0)
    if peak_sites:
        lines.append("  live bytes at peak, by site:")
        for site, nbytes in sorted(peak_sites.items(),
                                   key=lambda kv: -kv[1]):
            share = (nbytes / peak * 100.0) if peak else 0.0
            bar = "#" * int(round(share / 5.0))
            lines.append(f"    {site:<14s}{share:6.1f}%"
                         f"{nbytes:>14,d}  {bar}")
    owners = mem.get("peak_owners") or []
    if owners:
        lines.append("  owners at peak:")
        for o in owners[:8]:
            lines.append(f"    {str(o.get('query_id')):<22s}"
                         f"{str(o.get('site')):<12s}"
                         f"{str(o.get('op'))[:24]:<26s}"
                         f"{_fmt(o.get('bytes')):>14}")
    spill = mem.get("spill") or {}
    if any((spill.get(d) or {}).get("count") for d in spill):
        lines.append("  tier moves:")
        lines.append(f"    {'direction':<16s}{'count':>6s}"
                     f"{'bytes':>14s}{'ms':>10s}")
        for d in ("device_to_host", "host_to_disk", "unspill"):
            row = spill.get(d) or {}
            lines.append(f"    {d:<16s}{_fmt(row.get('count')):>6}"
                         f"{_fmt(row.get('bytes')):>14}"
                         f"{_fmt(row.get('ms')):>10}")
    ledger = mem.get("ledger") or []
    if ledger:
        shown = len(ledger)
        total = mem.get("ledger_records") or shown
        lines.append(f"  spill ledger (last {shown} of {total}):")
        lines.append(f"    {'direction':<16s}{'site':<12s}"
                     f"{'op':<22s}{'bytes':>12s}{'reason':<10s}"
                     f"{'rank':>5s}{'ms':>9s}")
        for r in ledger:
            lines.append(f"    {str(r.get('direction')):<16s}"
                         f"{str(r.get('site')):<12s}"
                         f"{str(r.get('op'))[:20]:<22s}"
                         f"{_fmt(r.get('nbytes')):>12}"
                         f" {str(r.get('reason')):<9s}"
                         f"{_fmt(r.get('rank')):>5}"
                         f"{_fmt(r.get('ms')):>9}")
    leaks = mem.get("leaks") or []
    if leaks:
        lines.append("  !! leaked registrations at query end:")
        for lk in leaks[:8]:
            lines.append(f"    buffer={lk.get('buffer_id')} "
                         f"tier={lk.get('tier')} "
                         f"bytes={lk.get('nbytes')} "
                         f"site={lk.get('site')} op={lk.get('op')} "
                         f"refcount={lk.get('refcount')} "
                         f"registered_at={lk.get('tag')}")
    return lines


def doctor_lines(rec: Dict) -> List[str]:
    """The cross-plane doctor section of one engine record: the
    primary-bottleneck verdict, the sum-to-100 contribution shares and
    the ranked Amdahl-headroom candidates mapped onto ROADMAP items —
    obs/doctor.py's event-log surface.  Placeholder-tolerant on
    pre-r12 logs (same convention as ``--memory`` on pre-r11 logs)."""
    doc = rec.get("doctor")
    if not doc:
        return ["  (no doctor verdict recorded — older log or "
                "spark.rapids.tpu.obs.doctor.enabled=false)"]
    lines = ["-- query doctor (cross-plane verdict) --"]
    lines.append(
        f"  primary bottleneck: {doc.get('primary_cause')} at "
        f"{_fmt(doc.get('primary_share_pct'))}% of the query window")
    shares = doc.get("shares") or {}
    if shares:
        lines.append("  contribution shares (sum to 100):")
        for cause, pct in sorted(shares.items(), key=lambda kv: -kv[1]):
            if not pct:
                continue
            bar = "#" * int(round(float(pct) / 5.0))
            lines.append(f"    {cause:<20s}{float(pct):6.1f}%  {bar}")
    cands = doc.get("headroom") or []
    if cands:
        lines.append("  modeled headroom per candidate fix "
                     "(Amdahl bound):")
        lines.append(f"    {'cause':<20s}{'share':>7s}{'bound':>8s}"
                     f"  {'roadmap':<9s}fix")
        for c in cands:
            item = c.get("roadmap_item")
            lines.append(
                f"    {str(c.get('cause')):<20s}"
                f"{_fmt(c.get('share_pct')):>6}%"
                f"  <={_fmt(c.get('bound_x'))}x"
                f"  {('item ' + str(item)) if item else '-':<9s}"
                f"{str(c.get('fix'))[:46]}")
            if c.get("evidence"):
                lines.append(f"      evidence: {c['evidence']}")
    flushes, pred = doc.get("flushes"), doc.get("predicted_flushes")
    if flushes is not None:
        line = f"  flushes={flushes} predicted={_fmt(pred)}"
        if pred is not None and pred != flushes:
            line += " [!! PV-FLUSH mismatch]"
        lines.append(line)
    if doc.get("stats_digest"):
        lines.append(f"  stats_digest={doc['stats_digest'][:16]}…")
    return lines


def cost_lines(rec: Dict) -> List[str]:
    """The device-compute cost (costplane) section of one engine
    record: per-program roofline rows (achieved rates, arithmetic
    intensity, verdict), padding-waste bars against the padded bucket
    capacities, and the doctor's device_compute sub-verdict split —
    obs/costplane.py's event-log surface.  Placeholder-tolerant on
    pre-r14 logs (same convention as ``--memory``/``--doctor``)."""
    cost = rec.get("costplane")
    if not cost:
        return ["  (no costplane recorded — older log or "
                "spark.rapids.tpu.obs.cost.enabled=false)"]
    lines = ["-- device-compute cost (roofline) --"]
    lines.append(
        f"  verdict={cost.get('verdict')} "
        f"achieved={_fmt(cost.get('achieved_gflops'))}GF/s,"
        f"{_fmt(cost.get('achieved_gbps'))}GB/s "
        f"padding_waste={_fmt(cost.get('padding_waste_pct'))}% "
        f"(peaks {_fmt(cost.get('peak_tflops'))}TF/s,"
        f"{_fmt(cost.get('peak_gbps'))}GB/s "
        f"from {cost.get('peak_source', 'conf')} "
        f"ridge={_fmt(cost.get('ridge_intensity'))} flop/B)")
    progs = cost.get("programs") or []
    if progs:
        lines.append(f"  {'program':<26s}{'bucket':>8s}{'disp':>6s}"
                     f"{'intensity':>10s}{'GF/s':>9s}{'GB/s':>9s}"
                     f"{'share':>9s}  {'verdict':<14s}src")
        for p in progs:
            lines.append(
                f"  {str(p.get('program')):<26s}"
                f"{_fmt(p.get('bucket')):>8}"
                f"{_fmt(p.get('dispatches')):>6}"
                f"{_fmt(p.get('intensity')):>10}"
                f"{_fmt(p.get('achieved_gflops')):>9}"
                f"{_fmt(p.get('achieved_gbps')):>9}"
                f"{_fmt(p.get('est_share_pct')):>8}%"
                f"  {str(p.get('verdict') or '-'):<14s}"
                f"{str(p.get('source') or '-')}")
        wasted = [p for p in progs
                  if p.get("padding_waste_pct") is not None]
        if wasted:
            lines.append("  padding waste (padded rows beyond the "
                         "effective batch), by program:")
            for p in sorted(wasted,
                            key=lambda q: -q["padding_waste_pct"]):
                pct = float(p["padding_waste_pct"])
                bar = "#" * int(round(pct / 5.0))
                lines.append(f"    {str(p.get('program')):<26s}"
                             f"{pct:6.1f}%  {bar}")
    uncosted = cost.get("uncosted_dispatches")
    if uncosted:
        lines.append(f"  uncosted_dispatches={uncosted} "
                     "(no static cost captured for these buckets)")
    doc = rec.get("doctor") or {}
    sub = doc.get("device_compute_breakdown")
    if sub:
        d = (doc.get("shares") or {}).get("device_compute")
        lines.append(
            f"  doctor device_compute={_fmt(d)}% splits: "
            f"compute_bound={_fmt(sub.get('compute_bound'))}% "
            f"memory_bound={_fmt(sub.get('memory_bound'))}% "
            f"padding_waste={_fmt(sub.get('padding_waste'))}%")
    return lines


def stats_lines(prof: Dict) -> List[str]:
    """Text sections for one record's StatsProfile (obs/stats.py)."""
    lines: List[str] = []
    stages = prof.get("superstages") or []
    if stages:
        lines.append("-- superstage device-time attribution --")
        for s in stages:
            lines.append(f"  {s.get('node')} (node "
                         f"{s.get('node_index')}): "
                         f"device_ms={_fmt(s.get('device_ms'))} "
                         f"flushes={_fmt(s.get('flushes'))}")
            shares = s.get("member_share") or {}
            dms = s.get("member_device_ms") or {}
            for k, share in shares.items():
                lines.append(f"    {k:<38s}{share * 100:6.1f}%"
                             f"{dms.get(k, 0.0):>11.2f}ms")
    exchanges = prof.get("exchanges") or []
    if exchanges:
        lines.append("-- exchange data statistics --")
        lines.append(f"  {'node':<26s}{'kind':<11s}{'rows':>10s}"
                     f"{'est_bytes':>12s}{'nulls':>8s}"
                     f"{'distinct':>10s}{'skew':>9s}")
        for e in exchanges:
            skew = e.get("skew") or {}
            ratio = skew.get("ratio")
            skew_cell = "-" if ratio is None else (
                f"{ratio}{'!' if skew.get('skewed') else ''}")
            lines.append(f"  {str(e.get('node')):<26s}"
                         f"{str(e.get('kind')):<11s}"
                         f"{_fmt(e.get('rows')):>10}"
                         f"{_fmt(e.get('est_bytes')):>12}"
                         f"{_fmt(e.get('null_count')):>8}"
                         f"{_fmt(e.get('distinct_est')):>10}"
                         f"{skew_cell:>9s}")
            if skew.get("skewed"):
                rows = [p.get("rows") for p in e.get("partitions", [])]
                lines.append(f"    partition rows: {rows}")
    disp = prof.get("dispatches") or {}
    if disp:
        lines.append("-- dispatch durations --")
        for site, d in disp.items():
            lines.append(f"  {site:<12s} count={d.get('count', 0):<6d} "
                         f"p50={_fmt(d.get('p50_ms'))}ms "
                         f"p95={_fmt(d.get('p95_ms'))}ms")
    return lines


def render_query_report(query_id, story: Dict,
                        trace_events: Optional[List[Dict]] = None,
                        show_stats: bool = False,
                        show_shuffle: bool = False,
                        show_memory: bool = False,
                        show_doctor: bool = False,
                        show_cost: bool = False) -> str:
    """One query's full text report."""
    lines = [f"=== query {query_id} " + "=" * 40]
    engine = story.get("engine", [])
    service = story.get("service", [])
    if service:
        lines.append("-- service story --")
        lines.extend("  " + s for s in _service_story(service))
    for i, rec in enumerate(engine):
        tag = f" (attempt record {i + 1}/{len(engine)})" \
            if len(engine) > 1 else ""
        head = (f"-- plan + time shares{tag}: "
                f"wall_ms={_fmt(rec.get('wall_ms'))} "
                f"sem_wait_ms={_fmt(rec.get('sem_wait_ms'))} "
                f"spill_bytes={_fmt(rec.get('spill_bytes'))}")
        if rec.get("flushes") is not None:
            # device round trips this query — the cost model the
            # planner predicts (columnar/pending.py)
            head += f" flushes={rec.get('flushes')}"
        pred = rec.get("predicted_flushes")
        if pred is not None:
            head += f" predicted_flushes={pred}"
            if rec.get("flushes") is not None and \
                    pred != rec.get("flushes"):
                # the static PV-FLUSH model disagreed with the runtime
                # counter — either the plan dispatched an unmodeled
                # barrier or the predictor regressed; both are bugs
                head += " [!! PV-FLUSH mismatch]"
        if rec.get("inline_compile_ms") is not None:
            head += (f" inline_compile_ms="
                     f"{rec.get('inline_compile_ms')}")
        if rec.get("device_util_pct") is not None:
            head += f" device_util_pct={rec.get('device_util_pct')}"
        if rec.get("plan_cache") is not None:
            # plan-cache disposition (cache/plan_cache.py): hit =
            # verify + PV-FLUSH replayed from the shape's stored
            # certificates; warm planner_path_ms ≪ cold is the win
            head += (f" plan_cache={rec.get('plan_cache')} "
                     f"planner_path_ms="
                     f"{_fmt(rec.get('planner_path_ms'))}")
        lines.append(head + " --")
        lines.extend(_format_plan(plan_time_shares(rec)))
        if rec.get("fallbacks"):
            lines.append("  CPU fallbacks:")
            lines.extend(f"    {f}" for f in rec["fallbacks"])
        lines.extend(util_lines(rec))
        lines.extend(obs_lines(rec))
        lines.extend(compile_lines(rec))
        if show_shuffle:
            lines.extend(shuffle_lines(rec))
        if show_memory:
            lines.extend(memory_lines(rec))
        if show_doctor:
            lines.extend(doctor_lines(rec))
        if show_cost:
            lines.extend(cost_lines(rec))
        if show_stats:
            prof = rec.get("stats_profile")
            if prof:
                lines.extend(stats_lines(prof))
            else:
                lines.append("  (no StatsProfile recorded — run with "
                             "spark.rapids.tpu.obs.stats.enabled=true)")
    if trace_events:
        spans = critical_spans(trace_events, query_id)
        if spans:
            lines.append("-- critical-path spans --")
            lines.append(f"  {'name':<28s}{'cat':<10s}"
                         f"{'count':>6s}{'total_ms':>12s}{'max_ms':>10s}")
            for s in spans:
                lines.append(f"  {s['name']:<28s}{s['cat']:<10s}"
                             f"{s['count']:>6d}{s['total_ms']:>12.3f}"
                             f"{s['max_ms']:>10.3f}")
    return "\n".join(lines)


def slo_header(stories: Dict) -> List[str]:
    """Per-tenant latency header over every terminal service record in
    the log: nearest-rank p50/p95/p99 of queue_wait + execute (the same
    end-to-end definition obs/slo.py uses)."""
    by_tenant: Dict[str, List[float]] = {}
    for story in stories.values():
        for rec in story.get("service", []):
            if rec.get("event") not in ("completed", "failed",
                                        "cancelled"):
                continue
            total = (float(rec.get("queue_wait_ms") or 0.0) +
                     float(rec.get("execute_ms") or 0.0))
            by_tenant.setdefault(
                str(rec.get("tenant") or "default"), []).append(total)
    if not by_tenant:
        return []

    def pctl(xs, q):
        i = min(len(xs) - 1, int(q * (len(xs) - 1) + 0.5))
        return xs[i]

    lines = ["=== per-tenant latency (SLO plane) " + "=" * 27]
    lines.append(f"  {'tenant':<16s}{'queries':>8s}{'p50_ms':>10s}"
                 f"{'p95_ms':>10s}{'p99_ms':>10s}")
    for tenant in sorted(by_tenant):
        xs = sorted(by_tenant[tenant])
        lines.append(f"  {tenant:<16s}{len(xs):>8d}"
                     f"{pctl(xs, 0.5):>10.1f}{pctl(xs, 0.95):>10.1f}"
                     f"{pctl(xs, 0.99):>10.1f}")
    return lines


def render_report(stories: Dict,
                  trace_events: Optional[List[Dict]] = None,
                  query_id=None, show_stats: bool = False,
                  show_shuffle: bool = False,
                  show_memory: bool = False,
                  show_doctor: bool = False,
                  show_cost: bool = False) -> str:
    ids = [query_id] if query_id is not None else sorted(
        stories, key=lambda q: str(q))
    parts = []
    if query_id is None:
        header = slo_header(stories)
        if header:
            parts.append("\n".join(header))
    for qid in ids:
        if qid not in stories:
            raise KeyError(f"query {qid!r} not in event log")
        parts.append(render_query_report(qid, stories[qid], trace_events,
                                         show_stats=show_stats,
                                         show_shuffle=show_shuffle,
                                         show_memory=show_memory,
                                         show_doctor=show_doctor,
                                         show_cost=show_cost))
    return "\n\n".join(parts)


def render_html(stories: Dict,
                trace_events: Optional[List[Dict]] = None,
                query_id=None, show_stats: bool = False,
                show_shuffle: bool = False,
                show_memory: bool = False,
                show_doctor: bool = False,
                show_cost: bool = False) -> str:
    """Self-contained single-file HTML wrapping the text report
    per-query (monospace <pre> sections with a query index)."""
    ids = [query_id] if query_id is not None else sorted(
        stories, key=lambda q: str(q))
    body = ["<h1>spark_rapids_tpu query report</h1>",
            "<ul>" + "".join(
                f'<li><a href="#q{_html.escape(str(q))}">'
                f"{_html.escape(str(q))}</a></li>" for q in ids) + "</ul>"]
    for qid in ids:
        txt = render_query_report(qid, stories[qid], trace_events,
                                  show_stats=show_stats,
                                  show_shuffle=show_shuffle,
                                  show_memory=show_memory,
                                  show_doctor=show_doctor,
                                  show_cost=show_cost)
        body.append(f'<h2 id="q{_html.escape(str(qid))}">'
                    f"query {_html.escape(str(qid))}</h2>")
        body.append(f"<pre>{_html.escape(txt)}</pre>")
    return ("<!DOCTYPE html><html><head><meta charset='utf-8'>"
            "<title>query report</title><style>"
            "body{font-family:sans-serif;margin:2em}"
            "pre{background:#f6f8fa;padding:1em;overflow-x:auto}"
            "</style></head><body>" + "\n".join(body) + "</body></html>")


def render_soak_report(report: Dict) -> str:
    """The ``--soak`` view: one soak run's QPS/p99 timeline with the
    injected fault windows annotated in-line, the per-tenant burn
    table, the steady-state verdict and the per-fault impact/recovery
    correlation — rendered from a ``SoakReport`` JSON artifact
    (service/soak.py, written by ``tools/soak.py --out``)."""
    lines = ["=== soak run " + "=" * 49]
    cfg = report.get("config") or {}
    lines.append(
        f"  duration_s={_fmt(cfg.get('duration_s'))} "
        f"qps_target={_fmt(cfg.get('qps'))} "
        f"rows={_fmt(cfg.get('rows'))} "
        f"tenants={','.join(cfg.get('tenants') or [])} "
        f"seed={_fmt(cfg.get('seed'))} "
        f"faults={len(cfg.get('faults') or [])}")
    tot = report.get("totals") or {}
    lines.append(
        f"  submitted={_fmt(tot.get('submitted'))} "
        f"completed={_fmt(tot.get('completed'))} "
        f"failed={_fmt(tot.get('failed'))} "
        f"shed={_fmt(tot.get('shed'))} "
        f"sha_mismatch={_fmt(tot.get('sha_mismatch'))} "
        f"qps_actual={_fmt(tot.get('qps_actual'))} "
        f"sustained_rows_s={_fmt(tot.get('sustained_rows_s'))}")
    lat = report.get("latency") or {}
    lines.append(
        f"  p50_ms={_fmt(lat.get('p50_ms'))} "
        f"p95_ms={_fmt(lat.get('p95_ms'))} "
        f"p99_ms={_fmt(lat.get('p99_ms'))} "
        f"shed_rate_pct={_fmt(report.get('shed_rate_pct'))} "
        f"leak_drift_bytes={_fmt(report.get('leak_drift_bytes'))}")
    steady = report.get("steady") or {}
    lines.append(
        f"  steady_state={'yes' if steady.get('steady') else 'no'} "
        f"ewma_ms={_fmt(steady.get('ewma_ms'))} "
        f"slope_pct={_fmt(steady.get('slope_pct'))} "
        f"converged={_fmt(steady.get('converge_count'))}x "
        f"losses={_fmt(steady.get('losses'))}")
    anomaly = report.get("anomaly") or {}
    lines.append(
        f"  anomaly breaches={_fmt(anomaly.get('breach_total'))} "
        f"false_positives={_fmt(anomaly.get('fp_total'))} "
        f"fp_rate_pct={_fmt(anomaly.get('fp_rate_pct'))}")

    tenants = (report.get("burn") or {}).get("tenants") or {}
    if tenants:
        lines.append("-- per-tenant burn rate --")
        lines.append(f"  {'tenant':<16s}{'queries':>8s}{'breaches':>9s}"
                     f"{'fast':>8s}{'slow':>8s}")
        for name in sorted(tenants):
            t = tenants[name]
            fast = float(t.get("fast") or 0.0)
            mark = "  [!! budget]" if fast >= 1.0 else ""
            lines.append(f"  {name:<16s}{_fmt(t.get('count')):>8}"
                         f"{_fmt(t.get('breaches')):>9}"
                         f"{fast:>8.2f}"
                         f"{float(t.get('slow') or 0.0):>8.2f}{mark}")

    timeline = report.get("timeline") or []
    if timeline:
        lines.append("-- timeline (per-bucket QPS / p99, faults "
                     "annotated) --")
        lines.append(f"  {'t_s':>6s}{'n':>5s}{'qps':>8s}"
                     f"{'p50_ms':>9s}{'p99_ms':>9s}{'shed':>6s}"
                     f"{'fail':>6s}  {'p99':<22s}faults")
        peak_p99 = max((float(b.get("p99_ms") or 0.0)
                        for b in timeline), default=0.0) or 1.0
        for b in timeline:
            p99 = float(b.get("p99_ms") or 0.0)
            bar = "#" * int(round(p99 / peak_p99 * 20))
            faults = ",".join(b.get("faults") or [])
            lines.append(
                f"  {float(b.get('t_s') or 0.0):>6.1f}"
                f"{_fmt(b.get('n')):>5}"
                f"{float(b.get('qps') or 0.0):>8.1f}"
                f"{_fmt(b.get('p50_ms')):>9}"
                f"{_fmt(b.get('p99_ms')):>9}"
                f"{_fmt(b.get('shed')):>6}"
                f"{_fmt(b.get('failed')):>6}  {bar:<22s}"
                + (f"[{faults}]" if faults else ""))

    windows = report.get("faults") or []
    lines.append("-- fault windows --")
    if windows:
        lines.append(f"  {'id':<32s}{'kind':<22s}{'at_s':>7s}"
                     f"{'end_s':>7s}{'p99_before':>11s}"
                     f"{'p99_during':>11s}{'p99_after':>10s}"
                     f"{'recovered':>10s}{'rec_s':>7s}")
        for w in windows:
            lines.append(
                f"  {str(w.get('id')):<32s}"
                f"{str(w.get('kind')):<22s}"
                f"{_fmt(w.get('at_s')):>7}"
                f"{_fmt(w.get('end_s')):>7}"
                f"{_fmt(w.get('p99_before_ms')):>11}"
                f"{_fmt(w.get('p99_during_ms')):>11}"
                f"{_fmt(w.get('p99_after_ms')):>10}"
                f"{'yes' if w.get('recovered') else 'NO':>10}"
                f"{_fmt(w.get('recovery_s')):>7}")
            if w.get("diag_bundle"):
                lines.append(f"    bundle={w['diag_bundle']}")
        lines.append(
            f"  fault_recovery_ratio="
            f"{_fmt(report.get('fault_recovery_ratio'))}")
    else:
        lines.append("  (no faults injected)")
    return "\n".join(lines)


def main(argv=None):
    argv = list(argv if argv is not None else sys.argv[1:])
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: report <event_log.jsonl> [--query QID] "
              "[--trace trace.json] [--html out.html] [--stats] "
              "[--shuffle] [--memory] [--doctor] [--cost] [--all]\n"
              "       report <soak_report.json> --soak",
              file=sys.stderr)
        return 1

    def _opt(flag):
        if flag in argv:
            i = argv.index(flag)
            v = argv[i + 1]
            del argv[i:i + 2]
            return v
        return None

    def _flag(flag):
        if flag in argv:
            argv.remove(flag)
            return True
        return False

    if _flag("--soak"):
        # the positional is a SoakReport JSON artifact, not an event
        # log — one self-contained view, no joins needed
        with open(argv[0]) as f:
            print(render_soak_report(json.load(f)))
        return 0

    qid = _opt("--query")
    trace_path = _opt("--trace")
    html_out = _opt("--html")
    # --all turns on every per-plane section in one go (each section
    # stays placeholder-tolerant, so --all is safe on any-age log)
    show_all = _flag("--all")
    show_stats = _flag("--stats") or show_all
    show_shuffle = _flag("--shuffle") or show_all
    show_memory = _flag("--memory") or show_all
    show_doctor = _flag("--doctor") or show_all
    show_cost = _flag("--cost") or show_all
    log_path = argv[0]
    stories = load_query_stories(log_path)
    trace_events = load_trace(trace_path) if trace_path else None
    # query ids are ints for session-local logs, strings for service ones
    if qid is not None and qid not in stories:
        try:
            if int(qid) in stories:
                qid = int(qid)
        except ValueError:
            pass
    if html_out:
        with open(html_out, "w") as f:
            f.write(render_html(stories, trace_events, qid,
                                show_stats=show_stats,
                                show_shuffle=show_shuffle,
                                show_memory=show_memory,
                                show_doctor=show_doctor,
                                show_cost=show_cost))
        print(f"wrote {html_out}")
    else:
        print(render_report(stories, trace_events, qid,
                            show_stats=show_stats,
                            show_shuffle=show_shuffle,
                            show_memory=show_memory,
                            show_doctor=show_doctor,
                            show_cost=show_cost))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
