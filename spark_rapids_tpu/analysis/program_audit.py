"""Jaxpr-level program auditor: device-purity verification of every
registered jitted program.

The engine's performance contract is that each registered program — the
six ``obs/compile_watch.py`` JIT caches (fused_project,
staged_compute, hash_aggregate, mesh_join, mesh_sort, mesh_aggregate)
plus the join probe/speculative-probe programs
and the exchange stats sketch — runs on device with NO host round
trips, NO accidental float math in exact-mode programs, and a bounded
number of fusion-breaking data movements.  Those properties hold by
construction today; nothing CHECKED them, so a stray
``jax.pure_callback`` or a float upcast buried five calls deep would
ship silently.  This module abstractly traces each program via
``jax.make_jaxpr`` over representative avals (no device execution of
the traced program — everything runs host-side under
``JAX_PLATFORMS=cpu``) and walks the jaxpr, recursing through
``pjit``/``scan``/``cond``/``while``/pallas sub-jaxprs:

==========  =============================================================
rule id     meaning
==========  =============================================================
AUD001      host callback primitive in a device program
            (``pure_callback``/``io_callback``/``debug_callback``/
            ``outside_call``): every call is a host round trip on the
            dispatch path the program exists to keep device-resident
AUD002      float-dtype intermediate in an EXACT-mode program (integer
            SQL semantics must not silently route through f32/f64 —
            the binary64 discipline; specs with intentional float math
            register ``exact=False``)
AUD003      data-dependent shape: the trace aborted concretizing a
            traced value (shape/branch depends on data => host sync to
            resolve) or a traced aval carries a non-static dimension
AUD004      fusion-breaker census: gather/scatter/transpose operation
            counts exceed the spec's per-site budget (each is a
            relayout XLA cannot fuse through; growth => a perf
            regression hiding in a refactor)
==========  =============================================================

Registration: each JIT-cache module declares a ``_audit_specs()``
provider next to the cache returning small :class:`AuditSpec` records
(program factory + representative avals + mode flags); the registry
here (``_PROVIDER_MODULES``) only names the modules, so the spec lives
with the code it audits.  Suppressions: an ``# audit: allow(RULE)``
comment on the spec's construction statement (or the line above it)
drops that rule for that spec — same discipline as the lint layer's
``# lint: allow``.

Findings use the lint layer's ``(rule, file:line, message)``
:class:`~.lint.Finding` shape, anchored at the spec registration site.
CLI: ``ci/audit.py`` (exit-nonzero, seeded negative fixtures).
"""
from __future__ import annotations

import importlib
import os
import re
import sys
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .lint import Finding

AUD001 = "AUD001"
AUD002 = "AUD002"
AUD003 = "AUD003"
AUD004 = "AUD004"

ALL_RULES = (AUD001, AUD002, AUD003, AUD004)

_ALLOW_RE = re.compile(r"#\s*audit:\s*allow\(([A-Z0-9, ]+)\)")

#: host-callback primitives (AUD001).  Matched by exact name or the
#: ``callback`` substring so renamed jax-internal variants still trip.
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "outside_call", "host_callback_call"}

#: fusion-breaker primitive families (AUD004): each forces a relayout /
#: arbitrary data movement XLA cannot fuse through.
_BREAKER_FAMILIES = ("gather", "scatter", "transpose", "sort")


def _breaker_family(prim_name: str) -> Optional[str]:
    for fam in _BREAKER_FAMILIES:
        if prim_name == fam or prim_name.startswith(fam + "-") or \
                prim_name.startswith(fam + "_"):
            return fam
    return None


class AuditSpec:
    """One registered program to audit.

    ``build`` is LAZY: it constructs (or drives, for programs built
    per-batch inside an exec) the real jitted callable and returns
    ``(fn, args, make_jaxpr_kwargs)`` where ``args`` are representative
    concrete arrays or ``jax.ShapeDtypeStruct`` avals.  Building may
    execute a tiny CPU workload to populate the cache the program lives
    in — the audited object is always the REAL cached program, never a
    re-implementation.

    ``exact=True`` arms AUD002 (integer SQL semantics — no float
    intermediates); programs with intentional float math (the stats
    sketch's exact-by-construction f32 log2) register ``exact=False``.
    ``budgets`` maps AUD004 families (``gather``/``scatter``/
    ``transpose``/``sort``) to their per-site operation ceilings;
    a missing family is unbudgeted.
    """

    __slots__ = ("name", "cache", "build", "exact", "budgets", "notes",
                 "path", "line")

    def __init__(self, name: str, cache: str,
                 build: Callable[[], Tuple],
                 exact: bool = True,
                 budgets: Optional[Dict[str, int]] = None,
                 notes: str = ""):
        self.name = name
        self.cache = cache
        self.build = build
        self.exact = exact
        self.budgets = dict(budgets or {})
        self.notes = notes
        frame = sys._getframe(1)
        self.path = frame.f_code.co_filename
        self.line = frame.f_lineno

    def __repr__(self):
        return f"AuditSpec({self.name}, cache={self.cache})"


#: modules declaring ``_audit_specs()`` next to their JIT caches.  The
#: registry names modules, not specs, so adding a program means adding
#: a provider entry where the cache lives plus one line here.
_PROVIDER_MODULES = (
    "spark_rapids_tpu.exec.fused",
    "spark_rapids_tpu.exec.staged",
    "spark_rapids_tpu.exec.tpu_aggregate",
    "spark_rapids_tpu.exec.tpu_join",
    "spark_rapids_tpu.exec.tpu_mesh_join",
    "spark_rapids_tpu.exec.tpu_mesh_sort",
    "spark_rapids_tpu.exec.tpu_mesh_aggregate",
    "spark_rapids_tpu.obs.stats",
)

#: every registered program name the audit must cover — asserted by
#: tests/test_audit.py so a new JIT cache cannot ship unaudited.
REQUIRED_PROGRAMS = frozenset({
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


def collect_specs() -> List[AuditSpec]:
    """Import every provider module and gather its specs."""
    specs: List[AuditSpec] = []
    for modname in _PROVIDER_MODULES:
        mod = importlib.import_module(modname)
        specs.extend(mod._audit_specs())
    return specs


def coverage_gaps(specs: Sequence[AuditSpec]) -> List[str]:
    """Required program names no spec covers (empty = full coverage)."""
    have = {s.name for s in specs}
    return sorted(REQUIRED_PROGRAMS - have)


def aot_coverage_gaps(specs: Optional[Sequence[AuditSpec]] = None,
                      ) -> List[str]:
    """Bucketed-program registry entries (compile/aot.py
    BUCKETED_PROGRAMS — the programs whose shapes the AOT lattice
    buckets and the warmup daemon pre-compiles) that no audit spec
    covers.  Must stay empty: a program cannot join the bucketed
    registry unaudited, and the registry cannot drift from
    REQUIRED_PROGRAMS silently (tests/test_audit.py asserts both)."""
    from ..compile.aot import BUCKETED_PROGRAMS
    have = {s.name for s in (collect_specs() if specs is None else specs)}
    return sorted(p for p in BUCKETED_PROGRAMS if p not in have)


# ---------------------------------------------------------------------------
# suppressions: # audit: allow(RULE) at the spec construction site
# ---------------------------------------------------------------------------

def spec_allowed_rules(spec: AuditSpec) -> frozenset:
    """Rules suppressed for ``spec`` by ``# audit: allow(...)`` comments
    on its construction statement (scanned until the statement's
    brackets balance) or on the line directly above it."""
    try:
        with open(spec.path) as f:
            lines = f.read().splitlines()
    except OSError:
        return frozenset()
    rules: set = set()
    idx = spec.line - 1
    if idx - 1 >= 0:
        m = _ALLOW_RE.search(lines[idx - 1])
        if m and lines[idx - 1].strip().startswith("#"):
            rules.update(r.strip() for r in m.group(1).split(","))
    depth = 0
    for ln in lines[idx:min(idx + 40, len(lines))]:
        m = _ALLOW_RE.search(ln)
        if m:
            rules.update(r.strip() for r in m.group(1).split(","))
        depth += ln.count("(") + ln.count("[") + ln.count("{")
        depth -= ln.count(")") + ln.count("]") + ln.count("}")
        if depth <= 0:
            break
    return frozenset(r for r in rules if r)


# ---------------------------------------------------------------------------
# jaxpr walking (recursive through pjit/scan/cond/while/pallas)
# ---------------------------------------------------------------------------

def _jaxprs_in(value):
    """Yield every Jaxpr held (possibly nested in containers) by one
    eqn param — pjit stores a ClosedJaxpr, scan/while store Jaxprs,
    cond stores a tuple of branches, pallas_call stores its kernel."""
    if hasattr(value, "jaxpr") and hasattr(value, "consts"):
        yield value.jaxpr          # ClosedJaxpr
    elif hasattr(value, "eqns") and hasattr(value, "invars"):
        yield value                # Jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _jaxprs_in(v)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _jaxprs_in(v)


def iter_eqns(jaxpr):
    """All eqns of ``jaxpr`` and, recursively, of every sub-jaxpr any
    eqn parameter carries."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _jaxprs_in(param):
                yield from iter_eqns(sub)


def _avals_of(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None:
            yield aval


def breaker_census(closed_jaxpr) -> Dict[str, int]:
    """Recursive gather/scatter/transpose/sort operation counts."""
    census: Dict[str, int] = {}
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        fam = _breaker_family(eqn.primitive.name)
        if fam is not None:
            census[fam] = census.get(fam, 0) + 1
    return census


# ---------------------------------------------------------------------------
# tracing + rules
# ---------------------------------------------------------------------------

def _is_concretization_error(exc: Exception) -> bool:
    mod = type(exc).__module__ or ""
    name = type(exc).__name__
    return mod.startswith("jax") and (
        "Tracer" in name or "Concretization" in name or
        "NonConcrete" in name)


def trace_spec(spec: AuditSpec):
    """Abstractly trace the spec's program.  Returns
    ``(closed_jaxpr, None)`` on success or ``(None, finding)`` when the
    trace aborts on a data-dependence (AUD003)."""
    import jax
    try:
        fn, args, kwargs = spec.build()
    except Exception as e:  # noqa: BLE001 - any builder failure is fatal
        raise AuditBuildError(
            f"audit spec {spec.name} failed to build: {e!r}") from e
    try:
        closed = jax.make_jaxpr(fn, **kwargs)(*args)
    except Exception as e:  # noqa: BLE001 - classified below
        if _is_concretization_error(e):
            return None, Finding(
                AUD003, spec.path, spec.line,
                f"[{spec.name}] trace aborted concretizing a traced "
                f"value (data-dependent shape/branch forces a host "
                f"sync): {type(e).__name__}")
        raise AuditBuildError(
            f"audit spec {spec.name} failed to trace: {e!r}") from e
    return closed, None


class AuditBuildError(RuntimeError):
    """A spec's builder or trace failed for a non-rule reason — the
    audit itself is broken, which must fail CI loudly rather than
    report a clean run."""


def audit_spec(spec: AuditSpec
               ) -> Tuple[List[Finding], Dict[str, int]]:
    """Run every rule over one spec.  Returns (findings, census) where
    census is the AUD004 fusion-breaker count by family (also returned
    for clean specs — bench/report surface it)."""
    import numpy as np
    findings: List[Finding] = []
    closed, aborted = trace_spec(spec)
    if aborted is not None:
        findings.append(aborted)
        allowed = spec_allowed_rules(spec)
        return [f for f in findings if f.rule not in allowed], {}

    callback_prims: Dict[str, int] = {}
    float_prims: Dict[str, int] = {}
    dynamic_prims: Dict[str, int] = {}
    for eqn in iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        if pname in _CALLBACK_PRIMS or "callback" in pname:
            callback_prims[pname] = callback_prims.get(pname, 0) + 1
        for aval in _avals_of(eqn):
            dt = getattr(aval, "dtype", None)
            if spec.exact and dt is not None and \
                    np.issubdtype(dt, np.floating):
                float_prims[f"{pname}:{np.dtype(dt).name}"] = \
                    float_prims.get(f"{pname}:{np.dtype(dt).name}", 0) + 1
            shape = getattr(aval, "shape", ())
            if not all(isinstance(d, int) for d in shape):
                dynamic_prims[pname] = dynamic_prims.get(pname, 0) + 1

    def _fmt(d: Dict[str, int]) -> str:
        return ", ".join(f"{k} x{v}" for k, v in sorted(d.items()))

    if callback_prims:
        findings.append(Finding(
            AUD001, spec.path, spec.line,
            f"[{spec.name}] host callback primitive(s) in a device "
            f"program: {_fmt(callback_prims)} — each call is a host "
            f"round trip on the dispatch path"))
    if float_prims:
        findings.append(Finding(
            AUD002, spec.path, spec.line,
            f"[{spec.name}] float-dtype intermediate(s) in an "
            f"exact-mode program: {_fmt(float_prims)} — integer SQL "
            f"semantics must not route through floats (register "
            f"exact=False only for intentional float math)"))
    if dynamic_prims:
        findings.append(Finding(
            AUD003, spec.path, spec.line,
            f"[{spec.name}] non-static dimension(s) in traced avals: "
            f"{_fmt(dynamic_prims)} — output shapes must be static so "
            f"dispatch never waits on data"))

    census = breaker_census(closed)
    for fam, budget in sorted(spec.budgets.items()):
        count = census.get(fam, 0)
        if count > budget:
            findings.append(Finding(
                AUD004, spec.path, spec.line,
                f"[{spec.name}] fusion-breaker budget exceeded: "
                f"{count} {fam} ops > budget {budget} — growth here is "
                f"a relayout-bound perf regression; re-fuse or raise "
                f"the budget deliberately"))

    allowed = spec_allowed_rules(spec)
    return [f for f in findings if f.rule not in allowed], census


class AuditReport:
    """Outcome of one full audit run."""

    __slots__ = ("findings", "audited", "census")

    def __init__(self, findings: List[Finding], audited: List[str],
                 census: Dict[str, Dict[str, int]]):
        self.findings = findings
        self.audited = audited
        self.census = census

    @property
    def ok(self) -> bool:
        return not self.findings


def audit_all(specs: Optional[Sequence[AuditSpec]] = None,
              repo_root: Optional[str] = None) -> AuditReport:
    """Audit every registered program (or an explicit spec list).

    Coverage is part of the contract: a missing REQUIRED_PROGRAMS entry
    is itself a finding, so deleting a provider cannot silently shrink
    the audited surface."""
    if specs is None:
        specs = collect_specs()
        for gap in coverage_gaps(specs):
            raise AuditBuildError(
                f"no audit spec covers required program {gap!r}")
    findings: List[Finding] = []
    audited: List[str] = []
    census: Dict[str, Dict[str, int]] = {}
    for spec in specs:
        f, c = audit_spec(spec)
        findings.extend(f)
        audited.append(spec.name)
        census[spec.name] = c
    if repo_root:
        for f in findings:
            if os.path.isabs(f.path):
                f.path = os.path.relpath(f.path, repo_root)
    return AuditReport(findings, audited, census)


# ---------------------------------------------------------------------------
# seeded negative fixtures (ci/audit.py --fixture, tests/test_audit.py):
# each builds a tiny program engineered to trip exactly one rule, so the
# gate's failure path is exercised on every CI run.
# ---------------------------------------------------------------------------

def seeded_negative_specs() -> Dict[str, AuditSpec]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    def _cb_build():
        def prog(x):
            return jax.pure_callback(
                lambda v: np.asarray(v) + 1,
                jax.ShapeDtypeStruct((8,), np.int64), x)
        return prog, (jax.ShapeDtypeStruct((8,), np.int64),), {}

    def _float_build():
        def prog(x):
            return (x.astype(jnp.float32) * 0.5).astype(jnp.int64)
        return prog, (jax.ShapeDtypeStruct((8,), np.int64),), {}

    def _dyn_build():
        def prog(x):
            if x[0] > 0:        # traced bool -> concretization abort
                return x + 1
            return x
        return prog, (jax.ShapeDtypeStruct((8,), np.int64),), {}

    def _breaker_build():
        def prog(x, idx):
            return jnp.take(x, idx) + jnp.take(idx, idx)
        return prog, (jax.ShapeDtypeStruct((8,), np.int64),
                      jax.ShapeDtypeStruct((8,), np.int32)), {}

    return {
        AUD001: AuditSpec("fixture_callback", "fixture", _cb_build),
        AUD002: AuditSpec("fixture_float", "fixture", _float_build),
        AUD003: AuditSpec("fixture_dynamic", "fixture", _dyn_build),
        AUD004: AuditSpec("fixture_breaker", "fixture", _breaker_build,
                          budgets={"gather": 1}),
    }
