"""Device-residency analyzer: interprocedural host-transfer escape
analysis with a runtime transfer-guard cross-check.

The reference plugin earns device residency with cuDF's explicit
``Table``/``HostColumnVector`` type boundary: a column is either on the
GPU or it is not, and crossing costs a visible copy.  In JAX the
boundary is implicit — ``np.asarray``, ``float()``, ``len()``,
``.tolist()``, branching on an array value, even an f-string all
silently force a device->host transfer and a dispatch-queue sync.  A
hidden pull stalls the host until the device drains everything the
value depends on, so residency discipline is THE precondition for the async device-resident rewrite (ROADMAP item 8):
it is only safe to overlap aggressively once we can *prove* no
undeclared sync survives on the drain spine.

This module supplies that proof twice over, the same belt-and-braces
split PV-FLUSH applies to dispatch counts:

**Static half** — an AST-based interprocedural escape analysis over the
execution spine (``exec/``, ``kernels/``, ``compile/``, ``shuffle/``,
``columnar/``, ``api/session.py``, ``obs/stats.py``).  It builds a
module-level call graph, propagates a device-value taint lattice
(``HOST < UNKNOWN < DEVICE_CONTAINER < DEVICE``) from the known
device-array producers — ``jnp.``/``lax.`` calls, jit-cache call
sites, columnar batch accessors, pending-pool ``.dev`` resolves —
through assignments, containers, subscripts and function returns
(fixed point over the call graph, so a helper that returns a device
array taints every caller), and flags every operation that forces a
transfer or sync:

==========  =========================================================
RES001      undeclared device->host transfer (``np.asarray`` /
            ``np.array`` on a device value, ``float``/``int``/
            ``bool``/``len`` coercions, ``.tolist()``/``.item()``/
            ``.block_until_ready()``/``device_get``, a device value
            in a branch condition or f-string)
RES002      the same sync while holding the device semaphore — it
            stalls every concurrent dispatcher, not just this query
RES003      the same sync inside a pipeline drain loop — it
            serializes the morsel pipeline once per iteration
==========  =========================================================

A transfer is legal only at a **declared site**: a ``with
residency.declared_transfer(site=...)`` region whose ``site`` names an
entry in the :data:`SITES` registry below (collect sink, shuffle
serialize, oracle comparison, spill/diag paths, ...), or a file-level
attribution via a site's ``covers_files`` (the seeded form of lint's
historical SYNC001 ``np.asarray`` allowlist — see below).  Registry
coverage is asserted both ways, a la the PR 10 program auditor:
:func:`coverage_gaps` returning anything is a test AND a CLI failure
(``ci/residency.py`` exits 2).

**Runtime half** — the cross-check that turns a static false negative
into a loud failure: :func:`guard_scope` wraps engine execution in
``jax.transfer_guard_device_to_host("disallow")`` (conftest forces it
for the whole tier-1 suite via ``SPARK_RAPIDS_TPU_FORCE_TRANSFER_
GUARD``), and only :func:`declared_transfer` regions lift it.  JAX
transfer guards are *thread-local*, so the scope is entered on the
session execute thread AND inside every pipeline pool worker
(``exec/pipeline.py``) — a pull on a morsel thread is as guarded as
one on the collect path.  Each declared entry bumps a process-wide
per-site counter under the FLUSH_COUNT counter-delta discipline;
the session deltas it per query and lands ``declared_transfers`` on
the event-log record next to ``flushes`` and the netplane's
``host_drop_tax_ms``, so the doctor can cite which declared site owns
the ``host_staging`` share.

**SYNC001 consolidation** — lint's regex-level SYNC001 rule is rebased
onto this module's sink classifier so the two passes cannot disagree:
the banned sync attrs, the numpy aliases and the justified-pull
allowlist all live here (:data:`HOST_SYNC_ATTRS`, :data:`NP_ALIASES`,
:data:`SYNC_NP_FILE_ALLOWLIST` — the last is *derived* from the
``covers_files`` of the seeded declared sites, so an allowlist entry
IS a declared site).  :func:`stale_sync_allowlist` prunes: any covered
file in which the taint engine can no longer prove a device-tainted
pull is reported stale and must be dropped from its site.
"""
from __future__ import annotations

import ast
import os
import sys
import threading
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional, Set, Tuple

__all__ = [
    "SITES", "Site", "declared_transfer", "guard_scope", "guard_enabled",
    "snapshot", "delta", "site_counts", "TRANSFER_COUNT",
    "UndeclaredTransferError",
    "analyze_source", "analyze_project", "coverage_gaps",
    "stale_sync_allowlist", "transfer_census", "host_sync_sites",
    "RES001", "RES002", "RES003", "ALL_RULES",
    "HOST_SYNC_ATTRS", "NP_ALIASES", "SYNC_NP_FILE_ALLOWLIST",
]

RES001 = "RES001"
RES002 = "RES002"
RES003 = "RES003"
ALL_RULES = (RES001, RES002, RES003)

# ---------------------------------------------------------------------------
# shared sink classifier (single source of truth for lint's SYNC001)
# ---------------------------------------------------------------------------

#: unambiguous host-synchronization APIs — banned on the spine outside
#: declared regions regardless of taint (they exist only to sync)
HOST_SYNC_ATTRS = ("device_get", "block_until_ready")

#: numpy module aliases for the asarray/array pull check (lint imports
#: this; keep in sync with repo import idiom)
NP_ALIASES = frozenset({"np", "_np", "numpy"})


class Site:
    """One declared-transfer registry entry.

    ``justification`` is the human contract — WHY a device->host pull
    is legal here.  ``covers_files`` attributes every device-tainted
    pull in those basenames to this site without a lexical ``with``
    region (the seeded form of lint's SYNC001 allowlist); the lexical
    form is still required at runtime for the transfer-guard lift.
    ``counted=False`` marks one-time/duplicate pulls (the encoding
    probe, the pending-pool race re-pull) excluded from the per-query
    exactness contract.
    """

    __slots__ = ("justification", "covers_files", "counted")

    def __init__(self, justification: str,
                 covers_files: Tuple[str, ...] = (),
                 counted: bool = True):
        self.justification = justification
        self.covers_files = tuple(covers_files)
        self.counted = counted


#: the declared-transfer registry.  Every ``declared_transfer(site=...)``
#: call site must name an entry here, and every entry must have at least
#: one lexical call site or a valid ``covers_files`` attribution —
#: :func:`coverage_gaps` asserts both directions.
SITES: Dict[str, Site] = {
    "pending_flush": Site(
        "the one-flush pool's fused pulls (columnar/pending.py): every "
        "host-visible value is staged and resolved in <=2 fused "
        "transfers per flush — the engine's sanctioned transfer path, "
        "whose per-query count PV-FLUSH pins exactly"),
    "pending_probe": Site(
        "one-time encoding self-check at first flush: round-trips "
        "probe arrays to verify the u32/f64 stream encodings before "
        "trusting them (columnar/pending.py _check_encoding)",
        counted=False),
    "pending_race": Site(
        "narrow pending-pool race: a concurrent flush captured the "
        "item but has not decoded it yet, so the reader re-pulls the "
        "same value directly — a duplicate of an already-counted "
        "pending_flush transfer (columnar/pending.py Staged.np)",
        counted=False),
    "collect_sink": Site(
        "result materialization at the collect boundary "
        "(api/session.py): staged output buffers become arrow tables "
        "after the stage's single fused flush"),
    "shuffle_serialize": Site(
        "contiguous-split serialize (shuffle/meta.py build_table_meta): "
        "every device buffer of a map batch is pulled and packed "
        "back-to-back into the shuffle blob — the cuDF "
        "contiguousSplit/MetaUtils.buildTableMeta role"),
    "shuffle_fit": Site(
        "partitioner host finalization (shuffle/partitioners.py): "
        "range-bound sample pulls and per-batch split-count words at "
        "the stage barrier"),
    "batch_concat": Site(
        "string/list concat at a batch boundary (columnar/batch.py): "
        "exact live bytes are gathered on host — the reference also "
        "round-trips host for shuffle concat of serialized batches"),
    "spill_d2h": Site(
        "catalog tier move (memory/catalog.py): device buffers pulled "
        "to the host tier under memory pressure, and spill-slice "
        "fetches re-pulled for shuffle reads"),
    "oracle_compare": Site(
        "CPU-oracle equality harness (tests/harness.py): the TPU "
        "result set is collected for row-by-row comparison against "
        "the pyarrow CPU engine"),
    "size_probe": Site(
        "output-capacity sizing sync: a kernel's exact output count "
        "(gather/explode/window extents, join match totals) is pulled "
        "once to choose the padded bucket capacity of the next "
        "dispatch (columnar/column.py, exec/tpu_window.py, "
        "exec/tpu_generate.py, kernels/join.py)"),
    # ---- seeded from lint's historical SYNC001 np.asarray allowlist:
    # each covered file's justified pulls attribute here, and the
    # runtime pulls carry the same site in a lexical declared region.
    "join_verify": Site(
        "verify-at-flush barrier: the join pulls count words ONCE per "
        "flush for gather-map surgery and outer-row backfill "
        "(SURVEY §speculative)",
        covers_files=("tpu_join.py",)),
    "sort_ooc": Site(
        "out-of-core merge staging: run sample keys and boundary "
        "counts come to host once per spill-merge round",
        covers_files=("tpu_sort.py",)),
    "mesh_collect": Site(
        "mesh collectives hand results back to the host once per SPMD "
        "program (the shard gather at program exit)",
        covers_files=("tpu_mesh_aggregate.py", "tpu_mesh_join.py",
                      "tpu_mesh_sort.py")),
    "mesh_reshard": Site(
        "mesh-entry resharding (exec/tpu_mesh_*.py): single-device "
        "arrays are device_put onto the SPMD mesh sharding at program "
        "entry — a device->device copy on real hardware, but XLA:CPU's "
        "shard path materializes the source host-side first, so the "
        "reshard rides a declared region (uncounted: not a true "
        "device->host transfer on the modeled accelerator)",
        counted=False),
    "strings_prep": Site(
        "host-side string offset/byte-table prep feeding device "
        "uploads (kernels/strings.py, expr/string_ops.py)",
        covers_files=("strings.py",)),
    "binary64_host_libm": Site(
        "transcendental tail on host libm (kernels/binary64.py): "
        "numpy IS the CPU oracle's implementation, so exp/log/sin/... "
        "round-trip eagerly for bit-identical results",
        covers_files=("binary64.py",)),
}

#: lint's SYNC001 ``np.asarray`` allowlist, DERIVED from the seeded
#: declared sites above — the consolidation contract: an allowlisted
#: file is exactly a file some registered site covers.
SYNC_NP_FILE_ALLOWLIST = frozenset(
    f for s in SITES.values() for f in s.covers_files)

_COVERS_BY_FILE: Dict[str, str] = {
    f: sid for sid, s in SITES.items() for f in s.covers_files}


# ---------------------------------------------------------------------------
# runtime half: declared-transfer counters + the transfer guard
# ---------------------------------------------------------------------------

#: process-wide declared-transfer count (counted sites only) — the same
#: counter-delta discipline as columnar/pending.FLUSH_COUNT: the
#: session snapshots around each query window and deltas
TRANSFER_COUNT = 0

_SITE_COUNTS: Dict[str, int] = {}
_COUNT_LOCK = threading.Lock()

#: env override forcing the runtime guard on (the tier-1 conftest sets
#: it; export SPARK_RAPIDS_TPU_FORCE_TRANSFER_GUARD=0 to switch off)
_FORCE_ENV = "SPARK_RAPIDS_TPU_FORCE_TRANSFER_GUARD"


class UndeclaredTransferError(RuntimeError):
    """An undeclared device->host pull ran while the residency guard
    was armed.  Wrap the pull in ``residency.declared_transfer(site=…)``
    (registering the site in :data:`SITES` with a justification) or
    hoist the sync off the guarded spine."""


# thread-local guard state: ``disallow`` depth armed by guard_scope,
# ``allow`` depth lifted by declared_transfer.  The native JAX
# transfer_guard is entered too (real protection on TPU backends), but
# on the XLA:CPU test backend device arrays are host-local and the
# native guard never fires — the interposer below supplies the
# equivalent tripwire so tier-1 actually exercises the contract.
_TLS = threading.local()
_INTERPOSER_LOCK = threading.Lock()
_interposer_installed = False


def _interposer_blocked(value) -> bool:
    if not getattr(_TLS, "disallow", 0) or getattr(_TLS, "allow", 0):
        return False
    try:
        from jax._src.array import ArrayImpl as _ArrayImpl
    except Exception:  # noqa: BLE001 — no jax, nothing to guard
        return False
    # concrete device arrays only: tracers under jit never transfer
    return isinstance(value, _ArrayImpl)


def _trip(what: str) -> None:
    # one-line provenance (outermost in-repo frame) so a trip whose
    # traceback a harness swallows — e.g. a worker thread funneling
    # exceptions into a result list — still names the pull site
    where = ""
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        if (os.sep + "spark_rapids_tpu" + os.sep in fn
                and "analysis" + os.sep + "residency" not in fn):
            where = f" at {os.path.basename(fn)}:{f.f_lineno}"
            break
        f = f.f_back
    raise UndeclaredTransferError(
        f"undeclared device->host transfer ({what}{where}) while the "
        f"residency transfer guard is armed: declare it via "
        f"residency.declared_transfer(site=...) with a registered site, "
        f"or hoist the sync off the drain spine (see docs/analysis.md)")


def _install_interposer() -> None:
    """Arm the CPU-backend tripwire once per process.

    Patches ``np.asarray``/``np.array`` (numpy reaches ArrayImpl data
    through the C buffer protocol, bypassing ``__array__``) and the
    ``ArrayImpl._value`` property (the funnel for ``float()``/``int()``
    /``.tolist()``/``jax.device_get``).  All patches are pass-through
    no-ops unless the calling thread is inside :func:`guard_scope` and
    outside every :func:`declared_transfer` region.
    """
    global _interposer_installed
    with _INTERPOSER_LOCK:
        if _interposer_installed:
            return
        import numpy as np
        from jax._src import array as _jarray

        orig_asarray, orig_array = np.asarray, np.array
        orig_value = _jarray.ArrayImpl._value

        def guarded_asarray(a, *args, **kwargs):
            if _interposer_blocked(a):
                _trip("np.asarray")
            return orig_asarray(a, *args, **kwargs)

        def guarded_array(a, *args, **kwargs):
            if _interposer_blocked(a):
                _trip("np.array")
            return orig_array(a, *args, **kwargs)

        @property
        def guarded_value(self):
            if _interposer_blocked(self):
                _trip("ArrayImpl materialization")
            return orig_value.fget(self)

        np.asarray = guarded_asarray
        np.array = guarded_array
        _jarray.ArrayImpl._value = guarded_value
        _interposer_installed = True


@contextmanager
def declared_transfer(site: str):
    """Enter a declared device->host transfer region.

    Validates ``site`` against :data:`SITES` (an unregistered site is a
    programming error and raises), bumps the per-site counter, and
    lifts the device-to-host transfer guard for the region — the ONLY
    sanctioned way to transfer while :func:`guard_scope` is active.
    The guard lift is dynamic (thread-local), so pulls in callees are
    covered too.  The region is the coarse span ``srt.pull`` (args:
    ``site``; obs/trace.py): the host blocked on the device and the
    copy back; the query's counter ``pull.<site>`` (+1) counts it.
    """
    spec = SITES.get(site)
    if spec is None:
        raise KeyError(
            f"undeclared residency site {site!r}: register it in "
            f"analysis/residency.py SITES with a justification")
    if spec.counted:
        global TRANSFER_COUNT
        with _COUNT_LOCK:
            TRANSFER_COUNT += 1
            _SITE_COUNTS[site] = _SITE_COUNTS.get(site, 0) + 1
    import jax
    from ..obs import trace as _trace
    _TLS.allow = getattr(_TLS, "allow", 0) + 1
    _trace.count("pull." + site)
    try:
        with _trace.span("srt.pull", "pool", True, site=site), \
                jax.transfer_guard_device_to_host("allow"):
            yield
    finally:
        _TLS.allow -= 1


def guard_enabled(conf=None) -> bool:
    """True when the scoped disallow-guard mode is on: the
    ``spark.rapids.tpu.analysis.residency.transferGuard`` conf, or the
    ``SPARK_RAPIDS_TPU_FORCE_TRANSFER_GUARD`` env force (the tier-1
    harness)."""
    env = os.environ.get(_FORCE_ENV)
    if env is not None:
        return env not in ("0", "false", "")
    if conf is not None:
        try:
            from ..config import RESIDENCY_GUARD
            return bool(conf.get(RESIDENCY_GUARD))
        except Exception:  # noqa: BLE001 — guard never fails a query
            return False
    return False


@contextmanager
def guard_scope(conf=None):
    """Scoped ``jax.transfer_guard_device_to_host("disallow")`` for one
    engine execution region (no-op unless :func:`guard_enabled`).

    Thread-local by JAX contract: the session enters it around the
    collect drain AND every pipeline pool worker enters it around its
    serve loop, so undeclared pulls fail loudly wherever they run.
    Host->device uploads are never guarded — only the d2h direction
    carries the hidden-sync hazard this module polices.
    """
    if not guard_enabled(conf):
        with nullcontext():
            yield
        return
    import jax
    _install_interposer()
    _TLS.disallow = getattr(_TLS, "disallow", 0) + 1
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield
    finally:
        _TLS.disallow -= 1


def snapshot() -> Tuple[int, Dict[str, int]]:
    """Marker for a per-query window (counter-delta discipline)."""
    with _COUNT_LOCK:
        return TRANSFER_COUNT, dict(_SITE_COUNTS)


def delta(marker: Tuple[int, Dict[str, int]]) -> Tuple[int, Dict[str, int]]:
    """(total, per-site) declared transfers since ``marker`` —
    exact when queries run serially, like every plane window."""
    total0, sites0 = marker
    with _COUNT_LOCK:
        total = TRANSFER_COUNT - total0
        per = {k: v - sites0.get(k, 0) for k, v in _SITE_COUNTS.items()
               if v - sites0.get(k, 0)}
    return total, per


def site_counts() -> Dict[str, int]:
    with _COUNT_LOCK:
        return dict(_SITE_COUNTS)


# ---------------------------------------------------------------------------
# static half: the taint lattice
# ---------------------------------------------------------------------------

HOST = 0            # proven host (numpy/pyarrow/literal/shape metadata)
UNKNOWN = 1         # no proof either way (params, foreign calls)
DEVICE_CONTAINER = 2  # python container holding device arrays
DEVICE = 3          # proven device array (jnp producer, accessor, ...)

#: jax module aliases whose rooted CALLS produce device arrays
_JAX_ALIASES = frozenset({"jnp", "lax", "jsp", "jax"})

#: jnp/jax calls that return host metadata (dtype lattice queries,
#: backend introspection) — NOT device arrays, whatever the args
_JAX_HOST_FNS = frozenset({
    "issubdtype", "isdtype", "iinfo", "finfo", "dtype", "result_type",
    "promote_types", "can_cast", "default_backend", "devices",
    "device_count", "local_device_count", "process_index",
})

#: pyarrow Array/ChunkedArray methods the columnar interop layer calls
#: on host-side arrow values — host results even when the receiver was
#: (conservatively) tainted by the accessor-attribute rule
_PA_HOST_METHODS = frozenset({
    "fill_null", "is_valid", "cast", "combine_chunks", "flatten",
    "field", "buffers", "to_pylist", "null_count", "dictionary_encode",
})

#: ubiquitous builtin-container / string method names: never resolve
#: these through the project call graph by bare name (a dict's
#: ``.keys()`` must not alias ``MapColumn.keys``)
_GENERIC_METHOD_NAMES = frozenset({
    "keys", "values", "items", "get", "append", "extend", "pop",
    "add", "update", "setdefault", "clear", "copy", "sort", "index",
    "count", "remove", "insert", "close", "join", "split", "strip",
    "format", "encode", "decode", "startswith", "endswith", "lower",
    "upper", "read", "write", "flush", "popleft", "appendleft",
})

#: attribute loads that yield HOST metadata regardless of receiver
_HOST_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "nbytes",
                         "np", "name", "itemsize", "kind", "str"})

#: columnar accessor convention: these attribute loads ARE device
#: arrays in the columnar substrate and the kernel layer (Column.data /
#: .validity / .offsets / .elements, Staged.dev everywhere)
_ACCESSOR_ATTRS = frozenset({"data", "validity", "offsets", "elements"})

#: modules (path substrings) where the accessor convention applies
_ACCESSOR_SCOPES = ("columnar", "kernels", "expr")

#: method calls that keep a device receiver on device (everything not
#: listed and not a sink propagates the receiver's taint anyway; this
#: set only documents the common ones)
_SINK_METHOD_ATTRS = frozenset({"tolist", "item"})

#: the execution spine the project pass walks
SPINE = ("exec", "kernels", "compile", "shuffle", "columnar",
         os.path.join("api", "session.py"),
         os.path.join("obs", "stats.py"))


class _FuncInfo:
    __slots__ = ("node", "rel", "qualname", "params", "jitted",
                 "returns_taint", "param_taints", "is_method")

    def __init__(self, node, rel: str, qualname: str, jitted: bool,
                 is_method: bool):
        self.node = node
        self.rel = rel
        self.qualname = qualname
        args = node.args
        names = [a.arg for a in args.posonlyargs + args.args]
        if is_method and names:
            names = names[1:]
        self.params = names
        self.jitted = jitted
        # lattice max over all return expressions (fixpoint-raised);
        # container-aware: a list of device arrays stays
        # DEVICE_CONTAINER so truthiness/len() on it never flags
        self.returns_taint = HOST
        self.param_taints: Dict[str, int] = {}
        self.is_method = is_method


def _is_jitted(node) -> bool:
    """``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)`` — a jitted
    body is traced, so nothing inside it can transfer at run time."""
    for dec in node.decorator_list:
        d = dec
        if isinstance(d, ast.Call):
            f = d.func
            if isinstance(f, ast.Name) and f.id == "partial" and d.args:
                d = d.args[0]
            else:
                d = f
        if isinstance(d, ast.Attribute) and d.attr == "jit":
            return True
        if isinstance(d, ast.Name) and d.id == "jit":
            return True
    return False


def _dotted(node) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _declared_site_of(item) -> Optional[str]:
    """Site id when a ``with`` item is ``[residency.]declared_transfer(
    <site>)``, else None."""
    ctx = item.context_expr
    if not isinstance(ctx, ast.Call):
        return None
    f = ctx.func
    name = f.attr if isinstance(f, ast.Attribute) else \
        f.id if isinstance(f, ast.Name) else None
    if name != "declared_transfer":
        return None
    for kw in ctx.keywords:
        if kw.arg == "site" and isinstance(kw.value, ast.Constant):
            return str(kw.value.value)
    if ctx.args and isinstance(ctx.args[0], ast.Constant):
        return str(ctx.args[0].value)
    return "<dynamic>"


def _is_sem_ctx(item) -> bool:
    """A ``with`` item that takes the device semaphore (``with sem:``,
    ``with self._semaphore:`` ...)."""
    ctx = item.context_expr
    if isinstance(ctx, ast.Call):
        ctx = ctx.func
    name = _dotted(ctx)
    last = name.rsplit(".", 1)[-1].lower()
    return "sem" in last


class _Sink:
    __slots__ = ("rule", "line", "message", "site")

    def __init__(self, rule, line, message, site=None):
        self.rule = rule
        self.line = line
        self.message = message
        self.site = site          # declared site id, None = finding


class _FuncTaint:
    """One function (or module) body walk: forward taint propagation
    with loop/semaphore/declared-region context, recording sinks."""

    def __init__(self, proj: "_Project", rel: str, info: Optional[_FuncInfo],
                 record: bool):
        self.proj = proj
        self.rel = rel
        self.base = os.path.basename(rel)
        self.info = info
        self.record = record
        self.env: Dict[str, int] = {}
        self.loop_depth = 0
        self.sem_depth = 0
        self.declared: List[str] = []
        self.returns_taint = HOST
        self.sinks: List[_Sink] = []
        self._seen: Set[Tuple] = set()
        if info is not None:
            for p in info.params:
                self.env[p] = info.param_taints.get(p, UNKNOWN)

    # -- sink bookkeeping ---------------------------------------------------

    def _sink(self, node, what: str):
        if not self.record:
            return
        key = (node.lineno, getattr(node, "col_offset", 0), what)
        if key in self._seen:     # loop bodies walk twice (taint carry)
            return
        self._seen.add(key)
        if self.declared:
            self.sinks.append(_Sink(None, node.lineno, what,
                                    site=self.declared[-1]))
            return
        site = _COVERS_BY_FILE.get(self.base)
        if site is not None:
            self.sinks.append(_Sink(None, node.lineno, what, site=site))
            return
        if self.sem_depth:
            rule, ctx = RES002, ("device->host sync under the device "
                                 "semaphore stalls every concurrent "
                                 "dispatcher")
        elif self.loop_depth:
            rule, ctx = RES003, ("device->host transfer inside a drain "
                                 "loop serializes the pipeline per "
                                 "iteration")
        else:
            rule, ctx = RES001, ("undeclared device->host transfer on "
                                 "the execution spine")
        self.sinks.append(_Sink(
            rule, node.lineno,
            f"{what}: {ctx} — wrap in residency.declared_transfer(...) "
            f"or hoist off the spine"))

    # -- expression taint ---------------------------------------------------

    def expr(self, node) -> int:    # noqa: C901 — one dispatch table
        if node is None or isinstance(node, ast.Constant):
            return HOST
        if isinstance(node, ast.Name):
            if node.id in NP_ALIASES or node.id == "pa":
                return HOST
            return self.env.get(node.id, UNKNOWN)
        if isinstance(node, ast.Attribute):
            vt = self.expr(node.value)
            if node.attr == "dev":
                return DEVICE
            if node.attr in _HOST_ATTRS:
                return HOST
            if isinstance(node.value, ast.Name) and \
                    node.value.id in _JAX_ALIASES:
                return HOST          # module constants (jnp.bool_, ...)
            if node.attr in _ACCESSOR_ATTRS and any(
                    s in self.rel for s in _ACCESSOR_SCOPES):
                return DEVICE
            if vt == DEVICE:
                return DEVICE
            return UNKNOWN if vt != HOST else HOST
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, (ast.BinOp,)):
            return max(self.expr(node.left), self.expr(node.right))
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return max(self.expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            t = self.expr(node.left)
            for c in node.comparators:
                t = max(t, self.expr(c))
            # `x is None` / `x in (...)` yield python bools, never
            # device scalars, whatever the operands
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                   for op in node.ops):
                return HOST
            return t
        if isinstance(node, ast.Subscript):
            self.expr(node.slice)
            vt = self.expr(node.value)
            if vt in (DEVICE, DEVICE_CONTAINER):
                return DEVICE
            return vt
        if isinstance(node, (ast.List, ast.Tuple, ast.Set)):
            t = HOST
            for e in node.elts:
                t = max(t, self.expr(e))
            return DEVICE_CONTAINER if t == DEVICE else t
        if isinstance(node, ast.Dict):
            t = HOST
            for v in node.values:
                if v is not None:
                    t = max(t, self.expr(v))
            return DEVICE_CONTAINER if t == DEVICE else t
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            return self._comp(node)
        if isinstance(node, ast.IfExp):
            tt = self.expr(node.test)
            if tt == DEVICE:
                self._sink(node, "branch condition on a device value")
            return max(self.expr(node.body), self.expr(node.orelse))
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.FormattedValue):
                    if self.expr(v.value) in (DEVICE, DEVICE_CONTAINER):
                        self._sink(v, "device value formatted into an "
                                      "f-string forces a transfer")
            return HOST
        if isinstance(node, ast.FormattedValue):
            return self.expr(node.value)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        if isinstance(node, (ast.Await, ast.YieldFrom)):
            return self.expr(node.value)
        if isinstance(node, ast.Yield):
            if node.value is not None:
                self.expr(node.value)
            return UNKNOWN
        if isinstance(node, ast.Lambda):
            return UNKNOWN
        if isinstance(node, ast.NamedExpr):
            t = self.expr(node.value)
            self.env[node.target.id] = t
            return t
        if isinstance(node, ast.Slice):
            for part in (node.lower, node.upper, node.step):
                if part is not None:
                    self.expr(part)
            return HOST
        return UNKNOWN

    def _iter_taint(self, t: int) -> int:
        """Taint of one element when iterating a value of taint ``t``."""
        if t in (DEVICE, DEVICE_CONTAINER):
            return DEVICE
        return t

    def _comp(self, node) -> int:
        saved = dict(self.env)
        for gen in node.generators:
            it = self.expr(gen.iter)
            self._bind(gen.target, self._iter_taint(it))
            for cond in gen.ifs:
                if self.expr(cond) == DEVICE:
                    self._sink(cond, "branch condition on a device value")
        if isinstance(node, ast.DictComp):
            self.expr(node.key)
            t = self.expr(node.value)
        else:
            t = self.expr(node.elt)
        self.env = saved
        return DEVICE_CONTAINER if t == DEVICE else t

    # -- calls --------------------------------------------------------------

    def _call(self, node: ast.Call) -> int:     # noqa: C901
        f = node.func
        # numpy pull: np.asarray / np.array on a device value
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id in NP_ALIASES and \
                f.attr in ("asarray", "array"):
            argt = max((self.expr(a) for a in node.args), default=HOST)
            self._kwargs(node)
            if argt in (DEVICE, DEVICE_CONTAINER):
                self._sink(node, f"np.{f.attr} pulls a device value to "
                                 f"host and serializes the dispatch "
                                 f"queue")
            return HOST
        if isinstance(f, ast.Attribute):
            if f.attr in HOST_SYNC_ATTRS:
                self._args(node)
                self._sink(node, f"'{f.attr}' forces a device->host "
                                 f"round trip")
                return HOST
            recv = self.expr(f.value)
            self._args(node)
            if f.attr in _SINK_METHOD_ATTRS:
                if recv == DEVICE:
                    self._sink(node, f"'.{f.attr}()' on a device value "
                                     f"forces a transfer")
                return HOST
            if f.attr == "device_buffers":
                return DEVICE_CONTAINER
            if isinstance(f.value, ast.Name) and \
                    f.value.id in _JAX_ALIASES:
                return HOST if f.attr in _JAX_HOST_FNS else DEVICE
            if isinstance(f.value, ast.Name) and \
                    f.value.id in NP_ALIASES:
                # every numpy function returns a host value (asarray/
                # array handled above as the pull sink)
                return HOST
            if f.attr in _PA_HOST_METHODS:
                return HOST
            # method resolution within the project: self.foo() /
            # obj.helper() by bare name — never for ubiquitous builtin
            # container/string method names (a dict's .keys() must not
            # alias a project method of the same name)
            if f.attr not in _GENERIC_METHOD_NAMES:
                callee = self.proj.returns_taint_by_name(f.attr) \
                    if self.proj is not None else None
                if callee is not None:
                    self._propagate_args(f.attr, node)
                    return callee
            if recv == DEVICE:
                return DEVICE
            return UNKNOWN
        if isinstance(f, ast.Name):
            if f.id in ("float", "int", "bool"):
                argt = max((self.expr(a) for a in node.args), default=HOST)
                if argt == DEVICE:
                    self._sink(node, f"'{f.id}()' on a device scalar "
                                     f"syncs via __array__")
                return HOST
            if f.id == "len":
                argt = max((self.expr(a) for a in node.args), default=HOST)
                if argt == DEVICE:
                    self._sink(node, "'len()' on a device value")
                return HOST
            if f.id in ("range", "enumerate", "zip", "sorted", "list",
                        "tuple", "dict", "set", "print", "str", "repr",
                        "min", "max", "sum", "abs", "isinstance",
                        "getattr", "hasattr", "type"):
                return max((self.expr(a) for a in node.args),
                           default=HOST) if f.id in (
                               "enumerate", "zip", "sorted", "list",
                               "tuple", "min", "max") else \
                    (self._args(node) or HOST)
            self._args(node)
            self._kwargs(node)
            if self.proj is not None:
                rd = self.proj.returns_taint_by_name(f.id)
                if rd is not None:
                    self._propagate_args(f.id, node)
                    return rd
            return UNKNOWN
        # call of a call / subscripted callable: evaluate, unknown
        self.expr(f)
        self._args(node)
        return UNKNOWN

    def _args(self, node: ast.Call):
        for a in node.args:
            self.expr(a)
        self._kwargs(node)

    def _kwargs(self, node: ast.Call):
        for kw in node.keywords:
            self.expr(kw.value)

    def _propagate_args(self, name: str, node: ast.Call):
        """Interprocedural param taint: a DEVICE argument taints the
        callee's positional param (drives the call-graph fixpoint)."""
        if self.proj is None:
            return
        taints = [self.expr(a) for a in node.args]
        self.proj.taint_params(name, taints)

    # -- statements ---------------------------------------------------------

    def _bind(self, target, taint: int):
        if isinstance(target, ast.Name):
            self.env[target.id] = taint
        elif isinstance(target, (ast.Tuple, ast.List)):
            # multi-target unpack: DEVICE (e.g. a jitted tuple result)
            # makes every element a device array, but DEVICE_CONTAINER
            # is a *mixed* aggregate — ("u32", [parts...]) — so its
            # elements degrade to UNKNOWN, not DEVICE
            if len(target.elts) > 1 and taint == DEVICE_CONTAINER:
                elem = UNKNOWN
            elif len(target.elts) > 1:
                elem = self._iter_taint(taint)
            else:
                elem = taint
            for e in target.elts:
                self._bind(e, elem)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, taint)
        # attribute/subscript stores: no env to update

    def stmts(self, body: List):
        for st in body:
            self.stmt(st)

    def stmt(self, node):       # noqa: C901 — one dispatch table
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return    # nested defs are analyzed as their own functions
        if isinstance(node, ast.Assign):
            t = self.expr(node.value)
            for tgt in node.targets:
                self._bind(tgt, t)
            return
        if isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._bind(node.target, self.expr(node.value))
            return
        if isinstance(node, ast.AugAssign):
            t = max(self.expr(node.value),
                    self.expr(ast.copy_location(
                        ast.Name(id=node.target.id, ctx=ast.Load()),
                        node))
                    if isinstance(node.target, ast.Name) else UNKNOWN)
            self._bind(node.target, t)
            return
        if isinstance(node, ast.Expr):
            self.expr(node.value)
            return
        if isinstance(node, ast.Return):
            if node.value is not None:
                self.returns_taint = max(self.returns_taint,
                                         self.expr(node.value))
            return
        if isinstance(node, (ast.If,)):
            if self.expr(node.test) == DEVICE:
                self._sink(node.test, "branch condition on a device "
                                      "value syncs via __bool__")
            self.stmts(node.body)
            self.stmts(node.orelse)
            return
        if isinstance(node, ast.While):
            if self.expr(node.test) == DEVICE:
                self._sink(node.test, "loop condition on a device value "
                                      "syncs via __bool__")
            self.loop_depth += 1
            for _ in range(2):          # loop-carried taint: two passes
                self.stmts(node.body)
            self.loop_depth -= 1
            self.stmts(node.orelse)
            return
        if isinstance(node, (ast.For, ast.AsyncFor)):
            it = self.expr(node.iter)
            self._bind(node.target, self._iter_taint(it))
            self.loop_depth += 1
            for _ in range(2):
                self.stmts(node.body)
            self.loop_depth -= 1
            self.stmts(node.orelse)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            pushed_sites = 0
            pushed_sem = 0
            for item in node.items:
                site = _declared_site_of(item)
                if site is not None:
                    self.declared.append(site)
                    pushed_sites += 1
                elif _is_sem_ctx(item):
                    self.sem_depth += 1
                    pushed_sem += 1
                else:
                    self.expr(item.context_expr)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars, UNKNOWN)
            self.stmts(node.body)
            for _ in range(pushed_sites):
                self.declared.pop()
            self.sem_depth -= pushed_sem
            return
        if isinstance(node, ast.Try):
            self.stmts(node.body)
            for h in node.handlers:
                self.stmts(h.body)
            self.stmts(node.orelse)
            self.stmts(node.finalbody)
            return
        if isinstance(node, ast.Assert):
            self.expr(node.test)
            if node.msg is not None:
                self.expr(node.msg)
            return
        if isinstance(node, (ast.Raise,)):
            if node.exc is not None:
                self.expr(node.exc)
            return
        if isinstance(node, ast.Delete):
            return
        # Import / Global / Nonlocal / Pass / Break / Continue: nothing


class _Project:
    """Module-level call graph + cross-function taint fixpoint."""

    def __init__(self):
        self.functions: List[_FuncInfo] = []
        self.by_name: Dict[str, List[_FuncInfo]] = {}
        self._dirty = True

    def add_module(self, rel: str, tree: ast.AST):
        def collect(node, prefix, in_class):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qn = f"{rel}:{prefix}{child.name}"
                    info = _FuncInfo(child, rel, qn, _is_jitted(child),
                                     in_class)
                    self.functions.append(info)
                    self.by_name.setdefault(child.name, []).append(info)
                    collect(child, f"{prefix}{child.name}.", False)
                elif isinstance(child, ast.ClassDef):
                    collect(child, f"{prefix}{child.name}.", True)
        collect(tree, "", False)

    def returns_taint_by_name(self, name: str) -> Optional[int]:
        """Lattice max of the return taints of every project function
        named ``name`` (jitted => DEVICE), None when unknown to the
        graph."""
        infos = self.by_name.get(name)
        if not infos:
            return None
        return max(DEVICE if i.jitted else i.returns_taint
                   for i in infos)

    def taint_params(self, name: str, arg_taints: List[int]):
        infos = self.by_name.get(name)
        if not infos:
            return
        for info in infos:
            for i, t in enumerate(arg_taints):
                if t == DEVICE and i < len(info.params):
                    p = info.params[i]
                    if info.param_taints.get(p, UNKNOWN) != DEVICE:
                        info.param_taints[p] = DEVICE
                        self._dirty = True

    def fixpoint(self):
        """Iterate returns_device / param taints to a fixed point over
        the call graph (bounded — the lattice only ever goes up)."""
        for _ in range(6):
            self._dirty = False
            for info in self.functions:
                if info.jitted:
                    continue
                ft = _FuncTaint(self, info.rel, info, record=False)
                ft.stmts(info.node.body)
                if ft.returns_taint > info.returns_taint:
                    info.returns_taint = ft.returns_taint
                    self._dirty = True
            if not self._dirty:
                return


# ---------------------------------------------------------------------------
# suppressions:  # residency: allow(RES00N, reason=...)
# ---------------------------------------------------------------------------

import re as _re

_ALLOW_RE = _re.compile(
    r"#\s*residency:\s*allow\((RES\d{3})\s*,\s*reason=([^)]+)\)")


def _suppressions(source: str) -> Dict[int, Set[str]]:
    """line -> suppressed rules.  Mirrors lint's convention: a trailing
    comment covers its own line; a comment-only line covers the next
    code line.  A reason is REQUIRED — an allow() without one is
    ignored (the finding stands)."""
    out: Dict[int, Set[str]] = {}
    lines = source.splitlines()
    for i, line in enumerate(lines, start=1):
        m = _ALLOW_RE.search(line)
        if not m or not m.group(2).strip():
            continue
        rules = {m.group(1)}
        if line.split("#", 1)[0].strip():
            out.setdefault(i, set()).update(rules)
        else:
            for j in range(i + 1, len(lines) + 1):
                if j > len(lines):
                    break
                if lines[j - 1].strip() and \
                        not lines[j - 1].strip().startswith("#"):
                    out.setdefault(j, set()).update(rules)
                    break
    return out


# ---------------------------------------------------------------------------
# analysis entry points
# ---------------------------------------------------------------------------

class DeclaredUse:
    """One sink attributed to a declared site (census row)."""

    __slots__ = ("site", "path", "line", "what")

    def __init__(self, site, path, line, what):
        self.site = site
        self.path = path
        self.line = line
        self.what = what


class ResidencyReport:
    __slots__ = ("findings", "declared_uses", "census", "call_sites",
                 "errors")

    def __init__(self, findings, declared_uses, census, call_sites,
                 errors):
        self.findings = findings
        self.declared_uses = declared_uses
        self.census = census
        self.call_sites = call_sites
        self.errors = errors


def _analyze_tree(proj: Optional[_Project], rel: str, tree: ast.AST,
                  source: str):
    """Sinks for one parsed module (project context optional)."""
    findings = []
    declared = []
    supp = _suppressions(source)
    from .lint import Finding

    def run(info: Optional[_FuncInfo], body):
        ft = _FuncTaint(proj, rel, info, record=True)
        ft.stmts(body)
        for s in ft.sinks:
            if s.site is not None:
                declared.append(DeclaredUse(s.site, rel, s.line,
                                            s.message))
            elif s.rule in supp.get(s.line, ()):
                pass
            else:
                findings.append(Finding(s.rule, rel, s.line, s.message))

    local = _Project()
    local.add_module(rel, tree)
    if proj is None:
        proj = local
        proj.fixpoint()
    run(None, [st for st in tree.body
               if not isinstance(st, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.ClassDef))])
    for info in proj.functions if proj is not local else local.functions:
        if info.rel != rel or info.jitted:
            continue
        run(info, info.node.body)
    return findings, declared


def analyze_source(source: str, path: str = "<string>"):
    """Single-buffer analysis (fixtures / planted-code checks): local
    call graph only.  Returns (findings, declared_uses)."""
    tree = ast.parse(source)
    return _analyze_tree(None, path, tree, source)


def _spine_files(repo_root: str) -> List[Tuple[str, str]]:
    pkg = os.path.join(repo_root, "spark_rapids_tpu")
    out = []
    for entry in SPINE:
        p = os.path.join(pkg, entry)
        if os.path.isfile(p):
            out.append((os.path.join("spark_rapids_tpu", entry), p))
        elif os.path.isdir(p):
            for name in sorted(os.listdir(p)):
                if name.endswith(".py"):
                    out.append((os.path.join("spark_rapids_tpu", entry,
                                             name),
                                os.path.join(p, name)))
    return out


def analyze_project(repo_root: Optional[str] = None) -> ResidencyReport:
    """Full interprocedural pass over the execution spine."""
    repo_root = repo_root or _repo_root()
    proj = _Project()
    parsed: List[Tuple[str, ast.AST, str]] = []
    errors: List[str] = []
    for rel, path in _spine_files(repo_root):
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src)
        except (OSError, SyntaxError) as e:
            errors.append(f"{rel}: {e}")
            continue
        proj.add_module(rel, tree)
        parsed.append((rel, tree, src))
    proj.fixpoint()
    findings, declared = [], []
    for rel, tree, src in parsed:
        f, d = _analyze_tree(proj, rel, tree, src)
        findings.extend(f)
        declared.extend(d)
    census: Dict[str, Dict[str, int]] = {}
    for d in declared:
        mod = census.setdefault(d.path, {})
        mod[d.site] = mod.get(d.site, 0) + 1
    for f in findings:
        mod = census.setdefault(f.path, {})
        mod[f.rule] = mod.get(f.rule, 0) + 1
    call_sites = _declared_call_sites(repo_root)
    findings.sort(key=lambda f: (f.path, f.line))
    return ResidencyReport(findings, declared, census, call_sites,
                           errors)


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _declared_call_sites(repo_root: str) -> Dict[str, List[Tuple[str, int]]]:
    """site id -> lexical ``declared_transfer`` call sites, scanned
    over the whole repo tree (engine + tests + tools + ci) so sites
    used by the harness count toward coverage."""
    out: Dict[str, List[Tuple[str, int]]] = {}
    scan_dirs = ("spark_rapids_tpu", "tests", "tools", "ci")
    roots = [os.path.join(repo_root, d) for d in scan_dirs]
    roots = [r for r in roots if os.path.isdir(r)]
    for root in roots:
        for dirpath, _dirs, names in os.walk(root):
            for name in sorted(names):
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                rel = os.path.relpath(path, repo_root)
                try:
                    with open(path, encoding="utf-8") as f:
                        src = f.read()
                    if "declared_transfer" not in src:
                        continue
                    tree = ast.parse(src)
                except (OSError, SyntaxError):
                    continue
                for node in ast.walk(tree):
                    if isinstance(node, (ast.With, ast.AsyncWith)):
                        for item in node.items:
                            site = _declared_site_of(item)
                            if site is not None:
                                out.setdefault(site, []).append(
                                    (rel, node.lineno))
    return out


def coverage_gaps(repo_root: Optional[str] = None) -> List[str]:
    """Full-coverage assertion over the declared-site registry (the PR
    10 auditor contract — ``coverage_gaps()==[]`` is a test and a CLI
    failure):

    - every registered site has a lexical ``declared_transfer`` call
      site somewhere in the repo, or a ``covers_files`` attribution
      whose files all exist in the package;
    - every lexical call site names a registered site;
    - this module is excluded from the call-site scan's self-matches.
    """
    repo_root = repo_root or _repo_root()
    gaps: List[str] = []
    call_sites = _declared_call_sites(repo_root)
    self_rel = os.path.join("spark_rapids_tpu", "analysis",
                            "residency.py")
    pkg_files: Set[str] = set()
    for dirpath, _dirs, names in os.walk(
            os.path.join(repo_root, "spark_rapids_tpu")):
        pkg_files.update(n for n in names if n.endswith(".py"))
    for sid, spec in sorted(SITES.items()):
        uses = [(p, ln) for p, ln in call_sites.get(sid, [])
                if p != self_rel]
        missing = [f for f in spec.covers_files if f not in pkg_files]
        if missing:
            gaps.append(f"site {sid!r}: covers_files entries "
                        f"{missing} do not exist in the package "
                        f"(stale attribution)")
        if not uses and not spec.covers_files:
            gaps.append(f"site {sid!r} is registered but never used: "
                        f"no declared_transfer({sid!r}) call site in "
                        f"the repo")
    for sid, sites_list in sorted(call_sites.items()):
        if sid == "<dynamic>":
            gaps.append(
                "declared_transfer with a non-literal site at "
                + ", ".join(f"{p}:{ln}" for p, ln in sites_list)
                + " (sites must be string literals for coverage)")
        elif sid not in SITES:
            gaps.append(
                f"declared_transfer({sid!r}) at "
                + ", ".join(f"{p}:{ln}" for p, ln in sites_list)
                + " names no registered site")
    return gaps


def stale_sync_allowlist(repo_root: Optional[str] = None) -> List[str]:
    """Allowlist prune check: covered files in which the taint engine
    can no longer prove a single device-tainted pull.  A non-empty
    result means the file's justification has rotted — drop it from
    its site's ``covers_files`` (and from lint's allowlist, which is
    derived from it)."""
    repo_root = repo_root or _repo_root()
    report = analyze_project(repo_root)
    live: Set[str] = set()
    for d in report.declared_uses:
        live.add(os.path.basename(d.path))
    # a lexical declared region in a covered file counts as live too
    for sid, sites_list in report.call_sites.items():
        spec = SITES.get(sid)
        if spec is None:
            continue
        for p, _ln in sites_list:
            base = os.path.basename(p)
            if base in spec.covers_files:
                live.add(base)
    return sorted(f for f in SYNC_NP_FILE_ALLOWLIST if f not in live)


def transfer_census(repo_root: Optional[str] = None) -> Dict[str, Dict]:
    """Per-module transfer map (the CLI's ``--census``): declared-site
    uses and rule hits keyed by module path."""
    return analyze_project(repo_root).census


# ---------------------------------------------------------------------------
# lint integration: SYNC001 rebased on the taint engine
# ---------------------------------------------------------------------------

def host_sync_sites(tree: ast.AST, rel: str = "<string>",
                    check_asarray: bool = True) -> List[Tuple[int, str]]:
    """SYNC001's sink set, computed by THE SAME classifier and taint
    walk the residency rules use (per-file call graph — all lint can
    see).  Returns (line, message) pairs:

    - ``device_get`` / ``block_until_ready``: always (they exist only
      to sync);
    - ``np.asarray`` / ``np.array``: when ``check_asarray`` and the
      argument is not PROVEN host — a device-tainted or unknown value
      pulls; a taint-proven host value (numpy/pyarrow/literal) cannot,
      and flagging it would make the two passes disagree.
    """
    out: List[Tuple[int, str]] = []
    proj = _Project()
    proj.add_module(rel, tree)
    proj.fixpoint()

    class _V(_FuncTaint):
        def _sink(self, node, what):        # noqa: ARG002
            pass                            # RES attribution not wanted

        def _call(self, node):
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr in HOST_SYNC_ATTRS:
                    out.append((node.lineno,
                                f"'{f.attr}' forces a device->host "
                                f"round trip in the hot path"))
                elif check_asarray and isinstance(f.value, ast.Name) \
                        and f.value.id in NP_ALIASES and \
                        f.attr in ("asarray", "array"):
                    argt = max((self.expr(a) for a in node.args),
                               default=HOST)
                    if argt != HOST:
                        out.append((node.lineno,
                                    "numpy asarray on (potentially "
                                    "device) data pulls to host and "
                                    "serializes the dispatch queue"))
            return super()._call(node)

    def run(info, body):
        v = _V(proj, rel, info, record=False)
        v.stmts(body)

    run(None, [st for st in tree.body
               if not isinstance(st, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.ClassDef))])
    for info in proj.functions:
        if not info.jitted:
            run(info, info.node.body)
    out.sort()
    return out
