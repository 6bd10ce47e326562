"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read.  Kept with the benchmark so that every PR reduces a trace
the same way; checked on a small recorded trace in ``tests/``.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran, ``XLA Modules`` one per program.  Busy
time is the union of the ``XLA Ops`` intervals (overlapping operations
count once), averaged over the chips the cell uses.  Host planes hold
the harness's own ``chipbench.*`` annotations and the runtime's
TraceMe spans on the same clock, which is how an idle gap gets the name
of what the host was doing in it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
PROGRAM_ID = re.compile(r"\(\d+\)$")      # jit_f(<fingerprint>) -> jit_f
SPAN_PREFIX = "chipbench."
TOP = 10
#: spans nest a few deep; the innermost one over a time is among the
#: last few that started before it
LOOK_BACK = 64


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _label_at(spans, starts, t):
    """Innermost span of ``spans`` (sorted by start) that covers ``t``."""
    i = bisect.bisect_right(starts, t)
    best = None
    for name, s, e in spans[max(0, i - LOOK_BACK):i]:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else None


def reduce_planes(planes, n_devices: int) -> dict:
    device, host = {}, []
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        if m:
            device[int(m.group(1))] = {ln.name: ln for ln in p.lines}
        elif p.name.startswith("/host:"):
            for ln in p.lines:
                host += _events(ln)
    if not device:
        raise ValueError("the trace holds no /device:TPU:<n> plane: "
                         f"{[p.name for p in planes]}")
    busy, programs, gaps_from = [], {}, None
    n_ops = 0
    for idx in sorted(device):
        lines = device[idx]
        ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        merged = _union([(s, e) for _, s, e in (ops or mods)])
        busy.append(sum(e - s for s, e in merged))
        n_ops += len(ops)
        for name, s, e in (mods or ops):
            name = PROGRAM_ID.sub("", name)
            programs[name] = programs.get(name, 0.0) + (e - s)
        if gaps_from is None and merged:
            gaps_from = merged
    if not any(busy):
        raise ValueError("no operation ran on a device in the trace")
    # idle gaps of the first busy device, named by the host's spans
    ours = sorted((ev for ev in host if ev[0].startswith(SPAN_PREFIX)),
                  key=lambda ev: ev[1])
    theirs = sorted((ev for ev in host
                     if not ev[0].startswith(SPAN_PREFIX)
                     and ev[2] > ev[1]), key=lambda ev: ev[1])
    ours_s = [ev[1] for ev in ours]
    theirs_s = [ev[1] for ev in theirs]
    gaps = {}
    for (_, e0), (s1, _) in zip(gaps_from, gaps_from[1:]):
        mid = (e0 + s1) / 2
        span = _label_at(ours, ours_s, mid) or "between queries"
        doing = _label_at(theirs, theirs_s, mid) or "python"
        key = f"{span} | {doing}"
        gaps[key] = gaps.get(key, 0.0) + (s1 - e0)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    used = max(n_devices, 1)
    return {"busy_s": sum(busy) / used / 1e9,
            "busy_s_by_device": [b / 1e9 for b in busy],
            "device_ops": top(programs), "idle_gaps": top(gaps),
            "n_device_ops": n_ops}


def reduce_file(path: str, n_devices: int) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, n_devices)


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    """The one ``*.xplane.pb`` that ``jax.profiler.start_trace`` wrote
    under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one xplane under {trace_dir}: {found}")
    return reduce_file(found[0], n_devices)
