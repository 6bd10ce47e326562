"""Performance regression sentinel over the ``BENCH_r*.json`` ledger.

ROADMAP records the bench trajectory ("the next scaling moves have
measured baselines to beat") but until this module nothing *enforced*
it: rounds r06-r10 were simply never recorded, and a silent
throughput regression would have shipped unnoticed.  The sentinel
turns the in-repo ``BENCH_r*.json`` files into a longitudinal ledger
and gates CI on a committed baseline:

- :func:`parse_record` / :func:`load_history` — tolerant loader for
  both bench record shapes that exist in-tree: the legacy harness
  wrapper (``{"n", "cmd", "rc", "tail", "parsed"}``) and a bare key
  set (one ``bench.py`` stdout JSON line).  Early rounds predate
  most of the current key set (r01-r05 did; they are deleted with the
  backend they were taken on, tests keep a synthetic one); the loader degrades to
  placeholder ``None`` values instead of crashing, so history tables
  always render every round.
- ``PERF_BASELINE.json`` — committed per-key baseline: value,
  direction (``higher`` / ``lower`` / ``exact``) and a noise band in
  percent, seeded from the newest recorded round.
- :func:`compare` — noise-aware comparison of a current record
  against the baseline: a ``higher`` key regresses below
  ``value * (1 - band)``, a ``lower`` key above ``value * (1 + band)``,
  an ``exact`` key (flush counts) on any mismatch; keys missing from
  the current record are *skipped* (placeholder tolerance), and a
  result beyond the band in the good direction is flagged as an
  improvement so ``ci/perf_gate.py`` can suggest a baseline bump.
  The per-key classification core lives in ``analysis/bands.py`` —
  shared verbatim with the online anomaly sentinel
  (``obs/anomaly.py``), so "regressed" means the same thing offline
  and live.

The CLI gate lives in ``ci/perf_gate.py``; on a regression it prints
the cross-plane doctor's verdict for the record
(``obs.doctor.diagnose_bench``), closing the loop from "a number got
worse" to "here is the bottleneck and the ROADMAP item that fixes
it".  Pure host-side file parsing: never imports jax, never touches
the device.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .bands import band_status

#: keys gated by default when seeding a baseline: (key, direction,
#: band_pct).  Directions: ``higher`` = higher is better (throughput),
#: ``lower`` = lower is better (taxes/latencies), ``exact`` = any
#: drift fails (flush counts are deterministic by construction —
#: PV-FLUSH cross-checks them statically).  Throughput bands sit
#: below 20% so the -20% seeded step ALWAYS trips (the default gate
#: compares committed ledger files, so machine jitter never enters);
#: tax bands are wide, with :data:`ABS_FLOORS` guarding the
#: zero-baseline case.
GATE_KEYS: Tuple[Tuple[str, str, float], ...] = (
    ("value", "higher", 15.0),
    ("exact_Mrows_s", "higher", 15.0),
    ("variable_Mrows_s", "higher", 15.0),
    ("pipeline_off_Mrows_s", "higher", 18.0),
    ("superstage_off_Mrows_s", "higher", 18.0),
    ("stats_off_Mrows_s", "higher", 18.0),
    ("flushes", "exact", 0.0),
    ("superstage_off_flushes", "exact", 0.0),
    ("predicted_flushes", "exact", 0.0),
    # device residency (analysis/residency.py): undeclared device->host
    # transfers the escape analysis proves on the execution spine, plus
    # registry coverage gaps.  Exact at 0 — a change that reintroduces
    # a hidden sync fails the perf gate, not a profiling session
    ("undeclared_transfers", "exact", 0.0),
    ("device_util_pct", "higher", 18.0),
    # AOT compile service (compile/aot.py): cold-start throughput of
    # the headline config, cold/warm spread (lower = persistent cache +
    # warmup absorbing compiles), JIT cache hit share, and how many
    # compiles the warmup daemon took off the query path (lower-bounded
    # by the floor — any count is fine, the key exists so the ledger
    # tracks it)
    ("cold_exact_Mrows_s", "higher", 18.0),
    ("cold_vs_warm_ratio", "lower", 150.0),
    ("compile_cache_hit_pct", "higher", 18.0),
    ("warmup_compiles", "lower", 400.0),
    ("host_drop_tax_ms", "lower", 150.0),
    ("spill_ms", "lower", 150.0),
    ("inline_compile_ms", "lower", 150.0),
    ("service_p99_ms", "lower", 150.0),
    # device-compute cost plane (obs/costplane.py): achieved HBM
    # bandwidth of the warm headline query (roofline numerator — a
    # throughput, so higher) and the padding-waste tax of the AOT
    # bucket lattice (the bucketRatio price; wide band + floor, the
    # waste share is shape-dependent noise at bench scale).  The
    # string ``roofline_verdict`` key rides the record but is not a
    # gate key (make_baseline skips non-numerics by design).
    ("achieved_GBps", "higher", 18.0),
    ("padding_waste_pct", "lower", 150.0),
    # longitudinal fleet plane (obs/history.py + obs/anomaly.py):
    # history rows are one-per-terminal-query by contract (exact, like
    # the flush counts), anomaly folds scale with rows x gated keys
    # (higher would mask a silently disabled sentinel), and the
    # background JSONL append must stay cheap (wide band + floor — a
    # p99 in single-digit ms is still off the query path, the gate
    # only catches an accidental sync write)
    ("history_rows", "exact", 0.0),
    ("anomaly_checks", "higher", 18.0),
    ("history_write_p99_us", "lower", 150.0),
    # plan cache + predictive scheduler (cache/plan_cache.py,
    # service/scheduler.py): the service burst's repeat hit rate
    # (higher — a drop means certificates stopped replaying), the cold
    # planner pass vs the certificate-replay warm path (both lower,
    # wide band + floor — sub-ms host timings jitter; the warm ≪ cold
    # relationship is what the pair documents), and the scheduler's
    # predicted-vs-actual exec_ms honesty mean (lower, very wide — the
    # EWMA baseline converges over rounds, the gate only catches a
    # model that stops predicting sanely)
    ("plan_cache_hit_pct", "higher", 18.0),
    ("planner_path_ms_cold", "lower", 150.0),
    ("planner_path_ms_warm", "lower", 150.0),
    ("predicted_exec_err_pct", "lower", 400.0),
    # observability self-cost (obs/overhead.py, bench.py planes-off
    # stage): headline throughput with every obs plane ON over the
    # same run with every plane OFF.  A ratio, already normalized, so
    # the band is DELIBERATELY tight (2% — the ≤2% total-overhead
    # budget): a 5% obs tax would hide inside the 15% throughput
    # bands above but trips here (the 0.95 seeded perf-gate fixture
    # pins exactly that)
    ("all_planes_on_vs_off", "higher", 2.0),
    # soak plane (service/soak.py, obs/burn.py, service/faults.py):
    # sustained mixed-traffic throughput and p99 through the service
    # under one seeded worker-kill fault (wide p99 band + floor —
    # service-burst latency at bench scale is host-jitter-dominated),
    # the open-loop shed share (lower, floored — a small shed count on
    # a saturated burst is fine, the gate catches the service starting
    # to refuse its steady load), the pool-idle-floor memory drift
    # over the run (EXACT 0 — a nonzero drift IS a leak; also
    # scale-invariant in ci/perf_gate.py so --run at any row count
    # still gates it), the anomaly sentinel's false-positive share
    # over stationary traffic (lower, floored — the sentinel must not
    # cry wolf on a steady soak), and the fraction of injected fault
    # windows whose p99 recovered (higher — 1.0 means every fault
    # healed within its guard window)
    ("sustained_Mrows_s", "higher", 18.0),
    ("soak_p99_ms", "lower", 150.0),
    ("shed_rate_pct", "lower", 150.0),
    ("leak_drift_bytes", "exact", 0.0),
    ("anomaly_fp_rate", "lower", 150.0),
    ("fault_recovery_ratio", "higher", 18.0),
)

#: keys scaled by the seeded perf-gate fixtures (throughput-like).
THROUGHPUT_KEYS = tuple(k for k, d, _b in GATE_KEYS if d == "higher")

#: absolute floors for ``lower``-direction keys.  A tax that measures
#: 0.0 in the baseline round (e.g. ``spill_ms`` when nothing spills)
#: would otherwise gate at ``0 * (1 + band) == 0`` and fail on any
#: positive jitter; the regression threshold is
#: ``max(value * (1 + band), abs_floor)``.
ABS_FLOORS = {
    "cold_vs_warm_ratio": 10.0,
    "warmup_compiles": 50.0,
    "host_drop_tax_ms": 5.0,
    "spill_ms": 5.0,
    "inline_compile_ms": 5.0,
    "service_p99_ms": 100.0,
    "padding_waste_pct": 50.0,
    "history_write_p99_us": 2000.0,
    "planner_path_ms_cold": 5.0,
    "planner_path_ms_warm": 5.0,
    "predicted_exec_err_pct": 50.0,
    "soak_p99_ms": 200.0,
    "shed_rate_pct": 20.0,
    "anomaly_fp_rate": 50.0,
}

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


@dataclass
class BenchRound:
    """One ledger row: a bench round with placeholder-tolerant keys."""
    round: int
    path: Optional[str] = None
    keys: Dict = field(default_factory=dict)

    def get(self, key: str):
        """Key value, or ``None`` placeholder when the round predates
        the key (the early-round gap-handling contract)."""
        return self.keys.get(key)


def parse_record(obj) -> Optional[Dict]:
    """Extract the bare key set from either record shape.

    Accepts the legacy wrapper (``{"n", "cmd", "rc", "tail",
    "parsed"}`` — ``parsed`` may be absent or null on a failed run),
    a bare key dict, or a JSON string of either.  Returns ``None``
    when no key set can be recovered (never raises on shape).
    """
    if obj is None:
        return None
    if isinstance(obj, (str, bytes)):
        try:
            obj = json.loads(obj)
        except (ValueError, TypeError):
            return None
    if not isinstance(obj, dict):
        return None
    if "parsed" in obj or ("cmd" in obj and "rc" in obj):
        parsed = obj.get("parsed")
        if isinstance(parsed, dict):
            return dict(parsed)
        # wrapper without a parsed block: last resort, fish the final
        # JSON line out of the captured tail
        tail = obj.get("tail")
        if isinstance(tail, str):
            for line in reversed(tail.strip().splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    try:
                        found = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(found, dict):
                        return found
        return None
    return dict(obj)


def load_round(path: str) -> Optional[BenchRound]:
    """One ``BENCH_r*.json`` file -> :class:`BenchRound` (or ``None``
    on unreadable/unparseable content — a placeholder row upstream)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            obj = json.load(f)
    except (OSError, ValueError):
        return None
    n = None
    if isinstance(obj, dict) and isinstance(obj.get("n"), int):
        n = obj["n"]
    if n is None:
        m = _ROUND_RE.search(os.path.basename(path))
        if m:
            n = int(m.group(1))
    if n is None:
        return None
    keys = parse_record(obj) or {}
    return BenchRound(round=n, path=path, keys=keys)


def load_history(root: str = ".") -> List[BenchRound]:
    """All in-repo bench rounds, sorted by round number.

    Missing rounds (r06-r10 were never recorded) simply do not
    appear; rounds whose files parse but predate the current key set
    appear with their partial key dict and ``.get()`` placeholders.
    """
    rounds: List[BenchRound] = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        r = load_round(path)
        if r is not None:
            rounds.append(r)
    rounds.sort(key=lambda r: r.round)
    return rounds


def history_table(rounds: List[BenchRound],
                  keys: Optional[List[str]] = None) -> List[Dict]:
    """Longitudinal ledger rows: one dict per round, every requested
    key present (``None`` placeholder where the round lacks it)."""
    if keys is None:
        keys = [k for k, _d, _b in GATE_KEYS]
    return [dict({"round": r.round}, **{k: r.get(k) for k in keys})
            for r in rounds]


# -- baseline ---------------------------------------------------------------

def make_baseline(record: Dict, *, round_n: int,
                  source: str = "", cmd: str = "",
                  rows: Optional[int] = None) -> Dict:
    """Seed a ``PERF_BASELINE.json`` dict from a bench key set: every
    :data:`GATE_KEYS` entry present in the record, with its default
    noise band."""
    keys = {}
    for key, direction, band in GATE_KEYS:
        val = record.get(key)
        if val is None or not isinstance(val, (int, float)):
            continue
        entry = {"value": val, "direction": direction}
        if direction != "exact":
            entry["band_pct"] = band
        if direction == "lower" and key in ABS_FLOORS:
            entry["abs_floor"] = ABS_FLOORS[key]
        keys[key] = entry
    return {"version": 1, "round": round_n, "source": source,
            "cmd": cmd, "rows": rows, "keys": keys}


def load_baseline(path: str) -> Dict:
    with open(path, "r", encoding="utf-8") as f:
        base = json.load(f)
    if not isinstance(base, dict) or "keys" not in base:
        raise ValueError(f"{path}: not a PERF_BASELINE file")
    return base


@dataclass
class Delta:
    """One gated key's comparison outcome."""
    key: str
    direction: str
    baseline: float
    band_pct: float
    current: Optional[float]
    status: str  # "ok" | "regression" | "improvement" | "skipped"
    message: str

    def __str__(self) -> str:
        return f"[{self.status:>11}] {self.key}: {self.message}"


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def compare(current: Dict, baseline: Dict) -> List[Delta]:
    """Noise-aware comparison of a current key set vs the baseline.

    Never raises on missing keys: a gated key absent from the current
    record is a ``skipped`` delta (the placeholder-tolerance contract
    shared with :func:`history_table`)."""
    out: List[Delta] = []
    for key, spec in baseline.get("keys", {}).items():
        base = spec["value"]
        direction = spec.get("direction", "higher")
        band = float(spec.get("band_pct", 0.0))
        cur = current.get(key)
        if cur is None or not isinstance(cur, (int, float)):
            out.append(Delta(key, direction, base, band, None, "skipped",
                             f"no current value (baseline {_fmt(base)})"))
            continue
        if direction == "exact":
            status = band_status(cur, base, "exact")
            if status == "regression":
                msg = f"expected exactly {_fmt(base)}, got {_fmt(cur)}"
            else:
                msg = f"{_fmt(cur)} (exact match)"
            out.append(Delta(key, direction, base, band, cur, status, msg))
            continue
        pct = (0.0 if base == 0 else (cur - base) / abs(base) * 100.0)
        detail = (f"{_fmt(cur)} vs baseline {_fmt(base)} "
                  f"({pct:+.1f}%, band ±{band:g}%)")
        status = band_status(cur, base, direction, band,
                             float(spec.get("abs_floor", 0.0)))
        out.append(Delta(key, direction, base, band, cur, status, detail))
    return out


def regressions(deltas: List[Delta]) -> List[Delta]:
    return [d for d in deltas if d.status == "regression"]


def improvements(deltas: List[Delta]) -> List[Delta]:
    return [d for d in deltas if d.status == "improvement"]


def seeded_record(baseline: Dict, scale: float) -> Dict:
    """A synthetic current record: every baseline throughput key
    scaled by ``scale``, everything else copied verbatim.  The perf
    gate's self-test fixtures (`--fixture regression` = 0.8,
    `--fixture improvement` = 1.5) are built from this, so the gate's
    own trip-wire is exercised on every CI run."""
    rec = {}
    for key, spec in baseline.get("keys", {}).items():
        val = spec["value"]
        if key in THROUGHPUT_KEYS and isinstance(val, (int, float)):
            rec[key] = round(val * scale, 6)
        else:
            rec[key] = val
    return rec
