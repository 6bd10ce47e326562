"""AOT compile subsystem — shape buckets, persistent reuse, warmup.

The reference plugin never pays kernel compilation on the query
critical path: cuDF kernels ship AOT-compiled in the jar.  Our XLA
backend compiles per novel (shape, dtype, conf) tuple inline, which
the compile-telemetry plane (obs/compile_watch.py) measures as
``inline_compile_ms`` per victim query.  This module is the fix for
ROADMAP open item 3 ("cold traffic"), in three parts:

**Shape-bucket lattice.**  Batch capacities were already padded to
powers of two (``columnar.column.bucket_capacity``); the lattice
generalizes the growth factor.  ``bucketRatio=2`` reproduces the
classic pow2 padding bit-for-bit; a coarser ratio (4) quarters the
number of distinct shapes every engine JIT cache compiles for, so
executables are shared across queries of different sizes.  Padding is
mask-correct by construction: every padded row carries a validity
word and live-row count, so bucketed results are sha-identical to
unbucketed execution (asserted by tests/test_aot.py across
pipelineParallelism x superstage).

**Persistent executable cache.**  JAX's persistent compilation cache
(directory owned by compile/xla_cache.py: ``JAX_COMPILATION_CACHE_DIR``
or the fixed in-checkout path) lets a fresh process deserialize prior
XLA executables instead of recompiling.  ``aot.cacheDir`` names where
this module keeps its *manifest*: one JSON entry per first-compile
keyed by ``sha1(program id | signature | conf fingerprint)`` — the
signature carries the dtype tuple and bucket, the fingerprint hashes
every program-affecting conf plus the jax version and lattice
geometry.  When a fresh process's first call of a program finds its
key in a manifest written by an *earlier* run against the same XLA
cache directory, the call is a persistent-cache load, not a compile: compile_watch counts it under
``tpu_compile_persistent_hits_total`` and keeps ``tpu_compile_seconds``
untouched (the cross-process test's "zero new XLA compiles"
assertion).

**Demand ledger + warmup registry.**  Call sites next to the JIT
caches report ``note_demand(cache, capacity, hit)`` per lookup; the
ledger keeps hit/miss counts per (program, bucket) and a thread-local
last-demand the telemetry plane uses to attribute a compile to its
bucket.  JIT caches register *warmers* — closures that call the real
jitted program with dummy arrays at a given bucket capacity (calling
is required: ``lower().compile()`` does not populate jit's C++
call-path cache).  The service's warmup daemon (service/warmup.py)
drains ``warm_missing()`` against the observed bucket mix, inside
``warmup_scope()`` so compile_watch attributes those compiles to the
``warmup`` pseudo-victim, never to a tenant query.

Hot-path discipline (SYNC001/OBS002/HYG002 lint scopes): the ledger
update is a dict poke under the GIL plus one bounded counter; no
device syncs, no wall-clock reads, manifest I/O happens outside the
module lock.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import uuid
from typing import Callable, Dict, List, Optional, Set, Tuple

from . import xla_cache
from ..obs import flight
from ..obs.registry import (AOT_BUCKET_DEMAND, AOT_HINT_COMPILES,
                            AOT_WARMUP_COMPILES)

#: every program participating in bucketed execution — the PR 10
#: auditor must keep full coverage over this registry
#: (analysis/program_audit.aot_coverage_gaps, tests/test_audit.py).
BUCKETED_PROGRAMS = frozenset({
    "fused_project",
    "staged_compute",
    "hash_aggregate_grouped",
    "hash_aggregate_whole_stage",
    "hash_aggregate_global",
    "join_probe",
    "join_spec_probe",
    "mesh_join",
    "mesh_sort",
    "mesh_aggregate",
    "exchange_stats",
})

_MANIFEST_NAME = "aot_manifest.json"
_SIG_MAX = 160


class BucketLattice:
    """Geometric capacity buckets: min_rows * ratio^k, smallest >= n."""

    def __init__(self, min_rows: int, ratio: int):
        if min_rows < 1:
            raise ValueError(f"lattice min_rows must be >= 1: {min_rows}")
        if ratio < 2 or (ratio & (ratio - 1)) != 0:
            raise ValueError(
                f"lattice ratio must be a power of two >= 2: {ratio}")
        self.min_rows = int(min_rows)
        self.ratio = int(ratio)

    def bucket(self, n: int) -> int:
        cap = self.min_rows
        while cap < n:
            cap *= self.ratio
        return cap

    def points_up_to(self, n: int) -> List[int]:
        """Every lattice point <= bucket(n) (smallest first)."""
        pts = [self.min_rows]
        while pts[-1] < n:
            pts.append(pts[-1] * self.ratio)
        return pts

    def __repr__(self):
        return f"BucketLattice(min={self.min_rows}, ratio={self.ratio})"


# ---------------------------------------------------------------------------
# module state (process-wide, last-configure-wins like the obs planes)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_ENABLED = True
_LATTICE: Optional[BucketLattice] = None
_CACHE_DIR = ""               #: where the manifest lives (aot.cacheDir)
_PERSIST_ALL = False          #: every program is written to the XLA cache
_CONF_FP = ""
_RUN_ID = uuid.uuid4().hex[:12]     #: distinguishes this process's
                                    #: manifest entries from prior runs

_MANIFEST: Dict[str, Dict] = {}     #: key -> entry (see manifest_add)
_MANIFEST_DIRTY = False

#: demand ledger: (cache, bucket) -> [hits, misses]
_DEMAND: Dict[Tuple[str, int], List[int]] = {}
#: (cache, bucket) pairs already seen (demanded or warmed): a fresh
#: demand against a seen pair is a hit — warmup converts misses to
#: hits, which is the whole point
_DEMAND_SEEN: Set[Tuple[str, int]] = set()
#: bound Prometheus children so the per-batch demand poke never
#: re-resolves labels
_DEMAND_CTR: Dict[Tuple[str, int, bool], object] = {}

#: warmers: program -> {variant: fn(bucket)} calling the real jitted
#: program (bounded per program; insertion-ordered, oldest evicted)
_WARMERS: Dict[str, Dict[str, Callable[[int], None]]] = {}
_WARMER_VARIANT_CAP = 8
#: (program, variant, bucket) triples already warmed (or attempted)
_WARMED: Set[Tuple[str, str, int]] = set()
_WARMUP_TOTAL = 0
_WARMUP_FAILED = 0

#: externally hinted (program, bucket) pairs awaiting pre-warm — the
#: predictive scheduler's PREDICTED demand (service/scheduler.py via
#: service/warmup.py note_hint), as opposed to the observed demand
#: ledger above.  A compile whose pair arrived ONLY through a hint is
#: counted under tpu_compile_hint_warmup_total, separate from the
#: admission-driven warmup counter.
_HINTS: Set[Tuple[str, int]] = set()
_HINTS_NOTED = 0
_HINT_COMPILES = 0

_TLS = threading.local()


# ---------------------------------------------------------------------------
# configure
# ---------------------------------------------------------------------------

def conf_fingerprint(conf) -> str:
    """Hash of every program-affecting conf plus the environment the
    traced HLO depends on (jax version, capacity floor, lattice
    geometry).  Observability/service/aot-bookkeeping groups are
    excluded: they never change a traced program, and including e.g.
    ``cacheDir`` itself would make every directory its own cold
    start."""
    import jax
    from ..columnar import column as _col
    from ..config import all_entries
    skip = ("spark.rapids.tpu.obs.", "spark.rapids.tpu.service.",
            "spark.rapids.tpu.compile.aot.", "spark.rapids.tpu.cache.",
            "spark.rapids.tpu.test.")
    h = hashlib.sha256()
    for e in all_entries():
        if any(e.key.startswith(p) for p in skip):
            continue
        h.update(f"{e.key}={conf.get(e)}\n".encode())
    lat = _LATTICE
    geom = (lat.min_rows, lat.ratio) if lat is not None else None
    h.update(f"jax={jax.__version__};min_cap={_col.MIN_CAPACITY};"
             f"lattice={geom}\n".encode())
    return h.hexdigest()[:16]


def configure(conf) -> None:
    """Apply the ``spark.rapids.tpu.compile.aot.*`` conf group
    (process-wide, last configure wins — the obs-plane discipline)."""
    global _ENABLED, _LATTICE, _CACHE_DIR, _CONF_FP, _PERSIST_ALL
    from ..columnar import column as _col
    from ..config import (AOT_BUCKET_RATIO, AOT_CACHE_DIR, AOT_ENABLED,
                          AOT_PERSIST_EVERY_PROGRAM)
    _ENABLED = bool(conf.get(AOT_ENABLED))
    if not _ENABLED:
        _LATTICE = None
        _col.set_bucket_fn(None)
        _CONF_FP = conf_fingerprint(conf)
        return
    _LATTICE = BucketLattice(_col.MIN_CAPACITY, int(conf.get(AOT_BUCKET_RATIO)))
    _col.set_bucket_fn(_LATTICE.bucket)
    _CONF_FP = conf_fingerprint(conf)
    d = str(conf.get(AOT_CACHE_DIR) or "").strip()
    if d and d != _CACHE_DIR:
        _CACHE_DIR = d
        os.makedirs(d, exist_ok=True)
        if bool(conf.get(AOT_PERSIST_EVERY_PROGRAM)):
            # the manifest's "an earlier run compiled this" claim only
            # holds when every program is persisted, however quick its
            # compile; the directory stays xla_cache's decision
            xla_cache.enable(persist_everything=True)
            _PERSIST_ALL = True
        _load_manifest()


def lattice() -> Optional[BucketLattice]:
    return _LATTICE


def enabled() -> bool:
    return _ENABLED


# ---------------------------------------------------------------------------
# persistent manifest
# ---------------------------------------------------------------------------

def manifest_key(cache: str, signature) -> str:
    sig = "" if signature is None else str(signature)[:_SIG_MAX]
    return hashlib.sha1(
        f"{cache}|{sig}|{_CONF_FP}".encode()).hexdigest()


def _manifest_path() -> str:
    return os.path.join(_CACHE_DIR, _MANIFEST_NAME)


def _load_manifest() -> None:
    path = _manifest_path()
    entries: Dict[str, Dict] = {}
    try:
        with open(path, "r", encoding="utf-8") as f:
            raw = json.load(f)
        # entries vouch for executables in ONE XLA cache directory; a
        # manifest written against another directory proves nothing
        if isinstance(raw, dict) and \
                raw.get("xla_cache_dir") == xla_cache.cache_dir():
            entries = {k: v for k, v in raw.get("entries", {}).items()
                       if isinstance(v, dict)}
    except (OSError, ValueError):
        entries = {}
    with _LOCK:
        _MANIFEST.clear()
        _MANIFEST.update(entries)


def _save_manifest() -> None:
    """Atomic rewrite; payload built under the lock, I/O outside it."""
    global _MANIFEST_DIRTY
    if not _CACHE_DIR:
        return
    with _LOCK:
        if not _MANIFEST_DIRTY:
            return
        payload = {"version": 1, "xla_cache_dir": xla_cache.cache_dir(),
                   "entries": dict(_MANIFEST)}
        _MANIFEST_DIRTY = False
    tmp = _manifest_path() + f".{_RUN_ID}.tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=0, sort_keys=True)
        os.replace(tmp, _manifest_path())
    except OSError:
        pass


def manifest_add(key: str, cache: str, signature, bucket: Optional[int],
                 dur_ms: float) -> None:
    """Record a first-compile into the manifest (and persist it)."""
    global _MANIFEST_DIRTY
    if not _CACHE_DIR:
        return
    sig = "" if signature is None else str(signature)[:_SIG_MAX]
    with _LOCK:
        _MANIFEST[key] = {"cache": cache, "signature": sig,
                          "bucket": bucket, "dur_ms": round(dur_ms, 3),
                          "run": _RUN_ID}
        _MANIFEST_DIRTY = True
    _save_manifest()


def persistent_ready(key: Optional[str]) -> bool:
    """True when this first-call should be satisfied by the persistent
    cache: the manifest entry was written by an EARLIER process run
    (same program id, signature and conf fingerprint) against the same
    XLA cache directory, with every program persisted."""
    if key is None or not _PERSIST_ALL:
        return False
    with _LOCK:
        e = _MANIFEST.get(key)
    return e is not None and e.get("run") != _RUN_ID


def first_call_key(cache: str, signature) -> Optional[str]:
    """Manifest key for a fresh first-call, or None when persistence
    is inactive (no cacheDir)."""
    if not _CACHE_DIR or not _ENABLED:
        return None
    return manifest_key(cache, signature)


def manifest_entries() -> int:
    with _LOCK:
        return len(_MANIFEST)


# ---------------------------------------------------------------------------
# demand ledger
# ---------------------------------------------------------------------------

def note_demand(cache: str, capacity: int,
                rows: Optional[int] = None) -> None:
    """One program invocation at a bucketed capacity (called on the
    batch path next to each JIT cache).  A first demand against an
    unseen (program, bucket) pair is a *miss* — the call that makes
    jit's shape-keyed cache build the per-bucket executable; every
    later demand (including the first, when warmup pre-compiled the
    pair) is a *hit*.  Feeds the per-bucket hit/miss ledger, the
    Prometheus bucket-demand counter, the thread-local last-demand
    the compile-telemetry plane reads to attribute a compile to its
    bucket, and the cost plane's dispatch ledger (``rows`` is the
    effective row count when the call site's host already knows it —
    obs/costplane.py padding-waste accounting)."""
    try:
        # the cost plane is its own plane with its own conf: dispatch
        # accounting runs even when the AOT ledger below is disabled
        from ..obs import costplane as _costplane
        _costplane.note_dispatch(cache, capacity, rows)
    except Exception:  # noqa: BLE001 — observability never fails a call
        pass
    if not _ENABLED:
        return
    cap = int(capacity)
    _TLS.last = (cache, cap)
    hit = (cache, cap) in _DEMAND_SEEN
    if not hit:
        _DEMAND_SEEN.add((cache, cap))
    cell = _DEMAND.get((cache, cap))
    if cell is None:
        # racy-create is benign under the GIL: two writers produce two
        # short-lived lists, the dict keeps one, counts stay plausible
        cell = [0, 0]
        _DEMAND[(cache, cap)] = cell
    cell[0 if hit else 1] += 1
    ctr = _DEMAND_CTR.get((cache, cap, hit))
    if ctr is None:
        ctr = AOT_BUCKET_DEMAND.labels(cache=cache, bucket=str(cap),
                                       outcome="hit" if hit else "miss")
        _DEMAND_CTR[(cache, cap, hit)] = ctr
    ctr.inc()


def last_demand(cache: str) -> Optional[int]:
    """The bucket of this thread's most recent demand for ``cache``
    (how note_compile learns the bucket without widening every
    wrap_miss call site)."""
    last = getattr(_TLS, "last", None)
    if last is not None and last[0] == cache:
        return last[1]
    return None


def demand_snapshot() -> Dict[str, List[int]]:
    """``{"cache|bucket": [hits, misses]}`` copy (sessions diff this
    around a query for the per-query bucket table)."""
    return {f"{c}|{b}": list(cell) for (c, b), cell in list(_DEMAND.items())}


def demanded_buckets() -> List[int]:
    """Every bucket observed in the demand mix (ascending)."""
    return sorted({b for (_c, b) in list(_DEMAND.keys())})


def note_hint(program: str, bucket: int) -> bool:
    """Predicted demand from the admission scheduler: mark a
    (program, bucket) pair worth pre-warming even though no tenant
    query has demanded it yet.  Pairs the demand ledger already saw
    are dropped (nothing left to predict).  Returns True when the
    hint was accepted."""
    if program not in BUCKETED_PROGRAMS:
        raise ValueError(f"unregistered bucketed program: {program}")
    global _HINTS_NOTED
    if not _ENABLED:
        return False
    pair = (program, int(bucket))
    if pair in _DEMAND_SEEN:
        return False
    _HINTS.add(pair)
    _HINTS_NOTED += 1
    return True


# ---------------------------------------------------------------------------
# warmup registry
# ---------------------------------------------------------------------------

def register_warmer(program: str, warm: Callable[[int], None],
                    variant: str = "default") -> None:
    """Register (or refresh) a warmer for one ``program`` variant (a
    distinct cache key — expression structure, dtype tuple): a
    closure that calls the real jitted callable with dummy arrays
    padded to a given bucket capacity.  Calling is the point — jit's
    call-path cache only populates on a real invocation.  Variants
    are bounded per program (oldest evicted), so warmup targets the
    recent program mix."""
    if program not in BUCKETED_PROGRAMS:
        raise ValueError(f"unregistered bucketed program: {program}")
    variants = _WARMERS.setdefault(program, {})
    variants.pop(variant, None)
    variants[variant] = warm
    while len(variants) > _WARMER_VARIANT_CAP:
        oldest = next(iter(variants))
        del variants[oldest]


def in_warmup() -> bool:
    return bool(getattr(_TLS, "warmup", False))


class warmup_scope:
    """Marks the calling thread as the warmup pseudo-victim: compiles
    recorded inside land under origin='warmup', never on a tenant
    query's inline_compile_ms (obs/compile_watch.py)."""

    def __enter__(self):
        self._prev = getattr(_TLS, "warmup", False)
        _TLS.warmup = True
        return self

    def __exit__(self, *exc):
        _TLS.warmup = self._prev
        return False


def warm_candidates() -> List[Tuple[str, str, int]]:
    """(program, variant, bucket) triples worth pre-compiling: every
    registered warmer crossed with every bucket in the observed
    demand mix, minus triples already warmed.  The cross product is
    the admission-aware prediction: engine pipelines run all their
    programs over the same batch buckets, so a bucket demanded by one
    program is imminent demand for the others."""
    buckets = demanded_buckets()
    out = []
    for program in sorted(_WARMERS.keys()):
        # hinted buckets extend the observed mix per program: the
        # scheduler predicted this pair, so pre-warm it even though
        # the ledger has never seen the bucket
        hinted = sorted({b for (p, b) in _HINTS if p == program})
        merged = sorted(set(buckets) | set(hinted))
        for variant in list(_WARMERS[program].keys()):
            for b in merged:
                if (program, variant, b) not in _WARMED:
                    out.append((program, variant, b))
    return out


def warm_one(program: str, variant: str, bucket: int) -> bool:
    """Run one warmer under the warmup scope.  The triple is marked
    warmed regardless of outcome so a failing warmer cannot
    retry-storm the background thread.  A successful warm also marks
    the (program, bucket) pair demand-seen: the next tenant demand
    against it counts as a hit."""
    global _WARMUP_TOTAL, _WARMUP_FAILED, _HINT_COMPILES
    warm = _WARMERS.get(program, {}).get(variant)
    _WARMED.add((program, variant, bucket))
    # hint-origin = the pair reached the candidate set ONLY through a
    # scheduler prediction (never organically demanded)
    hint_origin = (program, bucket) in _HINTS and \
        (program, bucket) not in _DEMAND
    _HINTS.discard((program, bucket))
    if warm is None:
        return False
    try:
        with warmup_scope():
            warm(bucket)
    except Exception:
        _WARMUP_FAILED += 1
        flight.record(flight.EV_COMPILE, "warmup_failed", bucket, 0)
        return False
    _WARMUP_TOTAL += 1
    _DEMAND_SEEN.add((program, bucket))
    if hint_origin:
        _HINT_COMPILES += 1
        AOT_HINT_COMPILES.labels(program=program).inc()
    else:
        AOT_WARMUP_COMPILES.labels(program=program).inc()
    flight.record(flight.EV_COMPILE, "warmup", bucket, 1)
    return True


def warm_missing(max_compiles: int) -> int:
    """Pre-compile up to ``max_compiles`` missing (program, variant,
    bucket) triples; returns how many warmers ran successfully."""
    done = 0
    for program, variant, bucket in warm_candidates():
        if done >= max_compiles:
            break
        if warm_one(program, variant, bucket):
            done += 1
    return done


def warmup_total() -> int:
    return _WARMUP_TOTAL


# ---------------------------------------------------------------------------
# surfaces
# ---------------------------------------------------------------------------

def stats_section() -> Dict:
    """The ``aot`` section of ``Service.stats().snapshot()``."""
    lat = _LATTICE
    with _LOCK:
        manifest_n = len(_MANIFEST)
    demand = {f"{c}|{b}": {"hit": cell[0], "miss": cell[1]}
              for (c, b), cell in sorted(_DEMAND.items())}
    return {
        "enabled": _ENABLED,
        "lattice": {"min_rows": lat.min_rows, "ratio": lat.ratio}
        if lat is not None else None,
        "cache_dir": _CACHE_DIR or None,
        "xla_cache_dir": xla_cache.cache_dir(),
        "xla_cache_persist_all": _PERSIST_ALL,
        "conf_fingerprint": _CONF_FP,
        "manifest_entries": manifest_n,
        "demand": demand,
        "warmers": {p: len(v) for p, v in sorted(_WARMERS.items())},
        "warmup_compiles": _WARMUP_TOTAL,
        "warmup_failed": _WARMUP_FAILED,
        "hints_noted": _HINTS_NOTED,
        "hints_pending": len(_HINTS),
        "hint_compiles": _HINT_COMPILES,
    }


def reset() -> None:
    """Test hook: drop ledger/warmer/manifest state and detach the
    lattice (keeps the process usable for unbucketed baselines)."""
    global _ENABLED, _LATTICE, _CACHE_DIR, _PERSIST_ALL, _CONF_FP
    global _WARMUP_TOTAL, _WARMUP_FAILED, _MANIFEST_DIRTY
    global _HINTS_NOTED, _HINT_COMPILES
    from ..columnar import column as _col
    with _LOCK:
        _MANIFEST.clear()
        _MANIFEST_DIRTY = False
    _DEMAND.clear()
    _DEMAND_SEEN.clear()
    _DEMAND_CTR.clear()
    _WARMERS.clear()
    _WARMED.clear()
    _HINTS.clear()
    _HINTS_NOTED = 0
    _HINT_COMPILES = 0
    _WARMUP_TOTAL = 0
    _WARMUP_FAILED = 0
    _ENABLED = True
    _LATTICE = None
    _CACHE_DIR = ""
    _PERSIST_ALL = False
    _CONF_FP = ""
    _col.set_bucket_fn(None)
    _TLS.last = None
    _TLS.warmup = False
