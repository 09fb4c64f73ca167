"""kt-xray: the abstract-interpreted compile-surface manifest.

The PR 4/8/9 warm-path guarantees — every live-path dispatch lands on a
pre-warmed shape, readbacks are explicit, the feature tensor stays
narrow — were *runtime* facts: the recompile watchdog counts a stall
after it happened, the sanity gate rejects garbage after the solve ran.
This module proves the compile surface **statically**: every jitted
entrypoint in the engine (``kubernetes_tpu/engine/entrypoints.py``) is
abstractly traced via ``jax.eval_shape`` / ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` inputs derived from the canonical bucket ladder
(``scheduler.bucket_ladder``) — **no device, no XLA compile** — into a
committed manifest (``tools/shape_manifest.json``): program → input /
output avals, donation state, and a jaxpr fingerprint.  A
compile-surface change then fails tier-1 on CPU instead of showing up
as a post-prewarm compile in a bench.

Rule passes over the jaxprs and sources (ids pinned by
tests/test_xray.py and the ARCHITECTURE.md rule inventory — kt-lint's
self-check protocol, so a rule cannot be silently deleted):

* **X01** — no host-sync/callback primitives (``pure_callback``,
  ``io_callback``, ``debug_callback``) reachable from a manifested
  program: a hidden host round-trip inside a solve body defeats the
  single-packed-readback discipline.
* **X02** — no silent dtype widening: ``convert_element_type`` to a
  float/int wider than the feature tensor's declared width (32 bits;
  ROADMAP item 2's narrower-dtype work will ratchet this down) inside a
  solve body silently doubles HBM and transfer bytes.
* **X03** — donation audit: every jit site under ``engine/`` carries a
  machine-readable ``# kt-xray: no-donate(<reason>)`` or ``# kt-xray:
  donate(<spec>)`` annotation matching its actual ``donate_argnums``
  (the deliberate non-donation of the dirty-row scatter,
  engine/solver.py ``_scatter_fn``, is the founding case).
* **X04** — ladder coverage: the manifest's warmed programs must equal
  ``scheduler.prewarm_plan``'s canonical plan, every AST-discovered jit
  site under ``engine/`` must be claimed by a registered entrypoint
  family, and every family's dispatch site must exist — "no unwarmed
  shapes" becomes a static theorem with the PR 9 watchdog demoted to
  runtime backstop (kept armed).

Protocol (kt-lint's): findings carry fingerprints; the manifest's
``justifications`` section grandfathers them with a mandatory reason;
stale justifications (finding fixed, entry left behind) fail; drift
(programs added / removed / fingerprint changed without regenerating
the manifest) always fails.  Regenerate with::

    python -m tools.ktxray --write-manifest

Tier-1 runs ``tools/check_manifest.py`` via tests/test_xray.py.

The canonical configuration is FIXED here (never env-derived): a knob
set in the environment must not make the committed manifest "drift".
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Optional

import jax
import numpy as np

from kubernetes_tpu.analysis import core as lint_core

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_MANIFEST = os.path.join(REPO, "tools", "shape_manifest.json")

# -- canonical configuration (fixed, never read from the environment) ----

#: The manifest's canonical instantiation.  These mirror the *defaults*
#: of the corresponding knobs/constants; a default change must be a
#: deliberate manifest regeneration (tests/test_xray.py pins the
#: correspondence), and an env override in the running process must
#: never move the committed surface.
CANON = {
    "schema": 1,
    "nodes": 8,                  # canonical fleet (canonical_rows() rows)
    "floor": 256,                # Scheduler.STREAM_MIN_BUCKET default
    "pad_limit": 4096,           # Scheduler._PAD_LIMIT
    "stream_threshold_off": True,  # KT_STREAM_CHUNK default 0
    "victims": 16,               # KT_PREEMPT_MAX_VICTIMS default, pow2
    "topo_terms": 1,             # one canonical spread term
    "topo_domains": 8,           # topology._pow2 domain floor
    "joint_iters": 24,           # solve_joint default n_iters
    # Declared feature-tensor widths (bits) — X02's widening bound.
    # The ISSUE-15 narrowing keeps solve ARITHMETIC at 32 bits (the
    # narrow wire planes widen exactly to int32 at every entrypoint —
    # never past it, which this bound still forbids); the narrowing
    # itself is recorded in the canonical cluster avals below
    # (NarrowCluster i16/u8 planes), so a plane silently widening back
    # to int32 storage IS manifest drift.
    "feature_bits": {"float": 32, "int": 32},
}

_DTYPE_SHORT = {
    "float64": "f64", "float32": "f32", "float16": "f16",
    "bfloat16": "bf16", "int64": "i64", "int32": "i32", "int16": "i16",
    "int8": "i8", "uint64": "u64", "uint32": "u32", "uint16": "u16",
    "uint8": "u8", "bool": "b1",
}


def aval_str(x: Any) -> str:
    """'f32[256x4]' for anything with .shape/.dtype."""
    name = np.dtype(x.dtype).name
    short = _DTYPE_SHORT.get(name, name)
    return f"{short}[{'x'.join(str(d) for d in x.shape)}]"


def _avals(tree: Any) -> list[str]:
    return [aval_str(leaf) for leaf in jax.tree_util.tree_leaves(tree)]


# -- X-rule registry ----------------------------------------------------

@dataclass(frozen=True)
class XRule:
    id: str
    title: str
    doc: str


XRULES: dict[str, XRule] = {}


def _xrule(rule_id: str, title: str, doc: str) -> XRule:
    r = XRule(rule_id, title, doc)
    XRULES[rule_id] = r
    return r


_xrule("X01", "no host-sync/callback primitives in manifested programs",
       doc="pure_callback/io_callback/debug_callback inside a solve "
           "body is a hidden host round-trip — every readback must be "
           "an explicit, accounted, gated site.")
_xrule("X02", "no silent dtype widening past the declared feature "
              "width",
       doc="convert_element_type to a wider float/int than the feature "
           "tensor's declared width silently doubles HBM and transfer "
           "bytes; narrowing work (ROADMAP 2) ratchets the bound down.")
_xrule("X03", "every engine jit site carries a donation annotation "
              "matching its donate_argnums",
       doc="Donation is a deliberate aliasing decision; an unannotated "
           "site hides whether the non-donation (or donation) was "
           "chosen or forgotten.")
_xrule("X04", "ladder coverage: warmed manifest == prewarm plan; no "
              "unmanifested jit entrypoints; dispatch sites exist",
       doc="Makes 'no live drain compiles after prewarm' a static "
           "theorem; the PR 9 recompile watchdog stays armed as the "
           "runtime backstop.")


@dataclass(frozen=True)
class XFinding:
    rule: str
    program: str   # program key, or repo-relative path for source rules
    message: str

    @property
    def fingerprint(self) -> str:
        return f"{self.rule}:{self.program}:{self.message}"

    def text(self) -> str:
        return f"{self.program}: {self.rule}: {self.message}"


# -- jaxpr helpers ------------------------------------------------------

def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Every eqn of a (Closed)Jaxpr, recursing into sub-jaxprs held in
    eqn params (pjit bodies, scan bodies, cond branches)."""
    inner = getattr(jaxpr, "jaxpr", None)
    if inner is not None:
        jaxpr = inner
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else (v,)
            for item in vals:
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    yield from iter_eqns(item)


def _canon_param(v: Any) -> str:
    """Canonical text for one eqn param value (sub-jaxprs recurse;
    callables print by name — a pure_callback's ``callback=<function at
    0x...>`` repr would otherwise bake a memory address in)."""
    from jax.extend import core as jax_core
    if isinstance(v, jax_core.ClosedJaxpr):
        return "{" + canonical_jaxpr(v.jaxpr) + "}"
    if isinstance(v, jax_core.Jaxpr):
        return "{" + canonical_jaxpr(v) + "}"
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_canon_param(x) for x in v) + ")"
    if isinstance(v, dict):
        return "{" + ",".join(f"{k}:{_canon_param(v[k])}"
                              for k in sorted(v)) + "}"
    if callable(v) and not isinstance(v, type):
        return f"fn:{getattr(v, '__name__', type(v).__name__)}"
    return repr(v)


def canonical_jaxpr(jaxpr: Any) -> str:
    """Deterministic serialization of a (Closed)Jaxpr.

    ``str(jaxpr)`` is NOT stable across process histories: the pretty
    printer hoists a sub-jaxpr into a shared named ``let`` binding only
    when the same ClosedJaxpr *object* appears twice, and that object
    identity depends on jax's internal tracing caches — a long test
    session can evict or repopulate them and flip the printed form
    (measured live: ``_where`` printed shared in a fresh process,
    inlined after a 200-test session).  This walks the IR directly:
    variables renamed in first-use order, eqn params sorted, sub-jaxprs
    recursed structurally — identical computation => identical text,
    whatever the printer would have shared."""
    inner = getattr(jaxpr, "jaxpr", None)
    if inner is not None:
        jaxpr = inner
    from jax.extend import core as jax_core
    names: dict = {}
    lines: list[str] = []

    def name(v: Any) -> str:
        if isinstance(v, jax_core.Literal):
            return f"lit({v.val!r})"
        if v not in names:
            names[v] = f"v{len(names)}"
        return names[v]

    lines.append("in=" + ",".join(
        f"{name(v)}:{v.aval}"
        for v in list(jaxpr.constvars) + list(jaxpr.invars)))
    for eqn in jaxpr.eqns:
        params = ";".join(f"{k}={_canon_param(eqn.params[k])}"
                          for k in sorted(eqn.params))
        ins = ",".join(name(v) for v in eqn.invars)
        outs = ",".join(f"{name(v)}:{v.aval}" for v in eqn.outvars)
        lines.append(f"{outs} = {eqn.primitive.name}[{params}] {ins}")
    lines.append("out=" + ",".join(name(v) for v in jaxpr.outvars))
    return "\n".join(lines)


def jaxpr_fingerprint(jaxpr: Any) -> str:
    """sha256 over the canonical serialization (``canonical_jaxpr``).
    Variable naming and eqn order are deterministic per trace, so the
    same source + same canonical avals + same jax build => same hash;
    anything that changes the traced computation changes it."""
    return "sha256:" + hashlib.sha256(
        canonical_jaxpr(jaxpr).encode()).hexdigest()


HOST_SYNC_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback")


def check_x01(program: str, jaxpr: Any) -> list[XFinding]:
    out = []
    seen = set()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in HOST_SYNC_PRIMITIVES and name not in seen:
            seen.add(name)
            out.append(XFinding(
                "X01", program,
                f"host-sync primitive '{name}' reachable from the "
                f"program body"))
    return out


def check_x02(program: str, jaxpr: Any,
              feature_bits: Optional[dict] = None) -> list[XFinding]:
    bits = feature_bits or CANON["feature_bits"]
    out = []
    seen = set()
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = np.dtype(eqn.params.get("new_dtype"))
        if new.kind == "f":
            limit = bits["float"]
        elif new.kind in ("i", "u"):
            limit = bits["int"]
        else:
            continue
        if new.itemsize * 8 > limit and new.name not in seen:
            seen.add(new.name)
            out.append(XFinding(
                "X02", program,
                f"convert_element_type to {new.name} widens past the "
                f"declared {limit}-bit feature width"))
    return out


# -- X03: the source-level donation audit -------------------------------

_JIT_CALLS = {"jax.jit", "jit", "pjit", "jax.pjit"}
_ANNOT_RE = re.compile(r"#\s*kt-xray:\s*(no-donate|donate)\b")


@dataclass(frozen=True)
class JitSite:
    path: str        # repo-relative
    func: str        # decorated function, or enclosing def for calls
    line: int        # annotation anchor line (decorator/call)
    donates: bool    # donate_argnums/donate_argnames present
    donate_spec: str = ""  # the kwarg value's source text ("" if none)

    @property
    def key(self) -> str:
        return f"{self.path}:{self.func}"


def _call_donation(call: Optional[ast.Call]) -> tuple[bool, str]:
    """(donates, spec source text) for a jit call's donation kwargs."""
    if call is None:
        return False, ""
    specs = [f"{kw.arg}={ast.unparse(kw.value)}"
             for kw in call.keywords
             if kw.arg in ("donate_argnums", "donate_argnames")]
    return bool(specs), ",".join(specs)


def discover_jit_sites(module: lint_core.Module) -> list[JitSite]:
    """Every jit site in one module: decorated defs (@jax.jit,
    @functools.partial(jax.jit, ...)) and jax.jit(fn) calls (keyed by
    their enclosing def — the _scatter_fn pattern)."""
    sites: list[JitSite] = []

    def visit(node: ast.AST, enclosing: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target, call = dec, None
                if isinstance(dec, ast.Call):
                    name = lint_core.call_name(dec)
                    call = dec
                    if name.endswith("partial") and dec.args:
                        target = dec.args[0]
                        if isinstance(target, ast.Call):
                            call = target
                            target = target.func
                    else:
                        target = dec.func
                if lint_core.dotted(target) in _JIT_CALLS:
                    donates, spec = _call_donation(
                        call if isinstance(call, ast.Call) else None)
                    sites.append(JitSite(
                        module.path, node.name, dec.lineno,
                        donates, spec))
            enclosing = node.name
        elif isinstance(node, ast.Call) and \
                lint_core.call_name(node) in _JIT_CALLS and node.args:
            donates, spec = _call_donation(node)
            sites.append(JitSite(module.path, enclosing, node.lineno,
                                 donates, spec))
        for child in ast.iter_child_nodes(node):
            visit(child, enclosing)

    visit(module.tree, "<module>")
    return sites


def _annotation_at(module: lint_core.Module,
                   line: int) -> Optional[str]:
    """'no-donate' | 'donate' from the site line or the run of comment
    lines directly above it (annotations read as a lead-in comment)."""
    for ln in range(line, 0, -1):
        text = module.lines[ln - 1]
        m = _ANNOT_RE.search(text)
        if m:
            return m.group(1)
        if ln != line and not text.strip().startswith("#"):
            return None
    return None


def check_x03(modules: list[lint_core.Module]) -> list[XFinding]:
    out = []
    for module in modules:
        if not module.path.startswith("kubernetes_tpu/engine/"):
            continue
        for site in discover_jit_sites(module):
            kind = _annotation_at(module, site.line)
            if kind is None:
                out.append(XFinding(
                    "X03", site.key,
                    "jit site has no '# kt-xray: no-donate(<reason>)' "
                    "/ 'donate(<spec>)' annotation"))
            elif kind == "no-donate" and site.donates:
                out.append(XFinding(
                    "X03", site.key,
                    "annotated no-donate but the jit call passes "
                    "donate_argnums/donate_argnames"))
            elif kind == "donate" and not site.donates:
                out.append(XFinding(
                    "X03", site.key,
                    "annotated donate but the jit call passes no "
                    "donate_argnums/donate_argnames"))
    return out


# -- canonical context & program tracing --------------------------------

def canonical_ladder() -> list[int]:
    from kubernetes_tpu.scheduler.scheduler import bucket_ladder
    return bucket_ladder(CANON["floor"], 1 << 62, CANON["pad_limit"], 0)


def canonical_rows() -> int:
    """Rows of the canonical cluster: the node axis' capacity for
    ``CANON['nodes']`` nodes (what the cache allocates and the mirror
    uploads)."""
    from kubernetes_tpu.features.compiler import capacity
    return capacity(CANON["nodes"])


def canonical_scatter_rows() -> list[int]:
    from kubernetes_tpu.engine.solver import ResidentCluster
    return ResidentCluster.scatter_buckets(canonical_rows())


def canonical_plan() -> list[str]:
    from kubernetes_tpu.scheduler.scheduler import prewarm_plan
    return prewarm_plan(canonical_ladder(), canonical_scatter_rows(),
                        joint=True, preempt=True, topo=True)


def _canonical_nodes() -> list:
    from kubernetes_tpu.api import types as api
    return [api.Node(
        name=f"__xray-{i}", labels={}, annotations={},
        allocatable_milli_cpu=4000, allocatable_memory=16 * 1024 ** 3,
        allocatable_gpu=0, allocatable_pods=110,
        conditions=[api.NodeCondition(type="Ready", status="True")])
        for i in range(CANON["nodes"])]


@dataclass
class Context:
    """The abstract template: ShapeDtypeStruct pytrees of the canonical
    batch/cluster, plus the solver whose policy constants the traces
    bake in."""
    solver: Any
    batch1: Any          # DeviceBatch avals at P=1
    cluster: Any         # DeviceCluster avals at canonical_rows() rows
    flags: Any
    scratch: dict = field(default_factory=dict)


def _absify(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.asarray(a).shape,
                                       np.asarray(a).dtype), tree)


def resize_pod_axis(b_abs: Any, p: int) -> Any:
    """The batch avals with the pod axis resized to ``p`` — the abstract
    counterpart of slice_pod_axis/pad, driven by the same field lists."""
    from kubernetes_tpu.engine import solver as sv

    def rz(s: jax.ShapeDtypeStruct) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((p,) + s.shape[1:], s.dtype)

    upd = {f: rz(getattr(b_abs, f)) for f in sv._POD_AXIS_FIELDS}
    aff = b_abs.aff._replace(**{f: rz(getattr(b_abs.aff, f))
                                for f in sv._AFF_POD_AXIS_FIELDS})
    vs = b_abs.volsvc._replace(**{f: rz(getattr(b_abs.volsvc, f))
                                  for f in sv._VS_POD_AXIS_FIELDS})
    return b_abs._replace(aff=aff, volsvc=vs, **upd)


def packed_avals(b_abs: Any, **riders: Any) -> Any:
    """The wire form the live dispatch hands the jitted entrypoints:
    ``solver.batch_layout`` over the batch avals and the launch's
    riders (live mask, tie counter, topology planes), as a PackedBatch
    of one carrier aval."""
    from kubernetes_tpu.engine import solver as sv
    layout, _leaves, words = sv.batch_layout(b_abs, **riders)
    return sv.PackedBatch(_sds((words,), np.int32), layout)


def build_context() -> Context:
    """One host-only feature compile of the canonical workload (a
    minimal pod over CANON['nodes'] identical nodes) through the REAL
    snapshot/compile machinery — so the template's ~70 array shapes can
    never drift from what the engine actually builds — then everything
    becomes ShapeDtypeStructs.  No device participation anywhere."""
    from kubernetes_tpu.api import types as api
    from kubernetes_tpu.cache.scheduler_cache import SchedulerCache
    from kubernetes_tpu.engine import solver as sv
    from kubernetes_tpu.engine.generic_scheduler import GenericScheduler
    from kubernetes_tpu.api.policy import (DEFAULT_MAX_EBS_VOLUMES,
                                           DEFAULT_MAX_GCE_PD_VOLUMES)
    cache = SchedulerCache()
    for node in _canonical_nodes():
        cache.add_node(node)
    eng = GenericScheduler(cache=cache)
    pods = [api.Pod(name="__xray-0", namespace="__xray__")]
    batch, hb, hc, _nt = eng._compile(pods, host_only=True)
    # The manifested cluster avals are the NARROW wire form the resident
    # mirror uploads — the committed manifest is the proof the narrowing
    # holds: a plane widening back to int32 storage changes in_avals and
    # drifts.
    with cache.lock:
        nt, agg, _ep, _nodes = cache.snapshot()
    hc = sv.narrow_cluster(hc, sv.narrow_policy(nt, agg, cache.space))
    # A FRESH solver (not the process-shared registry instance), with
    # the env-derived MaxPD caps pinned to their provider defaults: the
    # caps are compile-time constants baked into the jaxprs, and a
    # KUBE_MAX_PD_VOLS leak in some earlier test of the same process
    # must not make the committed manifest look drifted.
    import jax.numpy as jnp
    solver = sv.Solver(eng.policy)
    solver._half_dtype = jnp.float16  # canonical, backend-independent
    solver.extra = {"max_ebs": DEFAULT_MAX_EBS_VOLUMES,
                    "max_gce": DEFAULT_MAX_GCE_PD_VOLUMES}
    return Context(solver=solver, batch1=_absify(hb),
                   cluster=_absify(hc), flags=sv.batch_flags(hb))


def _sds(shape: tuple, dtype: Any) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


def program_builders(ctx: Context) -> dict[str, tuple[str, Callable,
                                                      tuple]]:
    """program key -> (family name, traceable fn, abstract args).

    The fns close over static values (solver, flags, n_iters) exactly
    as the runtime dispatch sites pass them, and call the *unjitted*
    underlying functions (``.__wrapped__``) so ``jax.make_jaxpr`` /
    ``jax.eval_shape`` interpret them abstractly."""
    from kubernetes_tpu.engine import solver as sv
    from kubernetes_tpu.engine.workloads import preemption, topology
    from kubernetes_tpu.ops import combine
    solver, flags = ctx.solver, ctx.flags
    n = canonical_rows()
    floor = CANON["floor"]
    cnt = _sds((), np.uint32)
    c_abs = ctx.cluster
    raw_scan = sv.Solver._solve_scan.__wrapped__
    raw_joint = sv.Solver._solve_joint_jit.__wrapped__
    raw_eval = sv.Solver.evaluate.__wrapped__
    raw_masks = sv.Solver.masks.__wrapped__
    raw_scatter = sv.ResidentCluster()._scatter_fn().__wrapped__
    raw_victim = preemption.victim_solve.__wrapped__
    raw_planes = topology._planes_kernel.__wrapped__

    progs: dict[str, tuple[str, Callable, tuple]] = {}

    # The batch arrives in its wire form (solver.PackedBatch): the live
    # mask rides its carrier, and so does the tie counter wherever the
    # host has it (a launch's first chunk, the one-shot solves, the
    # single-pod compile); a later chunk takes the previous scan's.
    def scan_first(b, c):
        return raw_scan(solver, b, c, None, None, flags, None)

    def scan_carry(b, c, k, cr):
        return raw_scan(solver, b, c, k, None, flags, cr)

    for bucket in canonical_ladder():
        b_abs = resize_pod_axis(ctx.batch1, bucket)
        live = _sds((bucket,), np.bool_)
        b_first = packed_avals(b_abs, live=live, counter=cnt)
        progs[f"scan_first@{bucket}"] = (
            "scan_first", scan_first, (b_first, c_abs))
        carry = jax.eval_shape(scan_first, b_first, c_abs)[2]
        progs[f"scan_carry@{bucket}"] = (
            "scan_carry", scan_carry,
            (packed_avals(b_abs, live=live), c_abs, cnt, carry))

    b_f = packed_avals(resize_pod_axis(ctx.batch1, floor),
                       live=_sds((floor,), np.bool_), counter=cnt)
    em = _sds((floor, n), np.bool_)
    sb = _sds((floor, n), np.float32)

    def oneshot_topo(b, c, m, s):
        return raw_scan(solver, b, c, None, s, flags, None, None, m)

    progs[f"oneshot_topo@{floor}"] = (
        "oneshot_topo", oneshot_topo, (b_f, c_abs, em, sb))

    def joint(b, c):
        return raw_joint(solver, b, c, None, None, None, None,
                         CANON["joint_iters"], flags)

    progs[f"joint@{floor}"] = ("joint", joint, (b_f, c_abs))

    b_1 = packed_avals(ctx.batch1, counter=cnt)
    progs["single_evaluate@1"] = (
        "single_evaluate", lambda b, c: raw_eval(solver, b, c, flags),
        (b_1, c_abs))
    progs["single_masks@1"] = (
        "single_masks", lambda b, c: raw_masks(solver, b, c),
        (b_1, c_abs))
    progs["select_hosts@1"] = (
        "select_hosts", combine.select_hosts,
        (_sds((1, n), np.float32), _sds((1, n), np.bool_), cnt))

    # The dirty rows arrive as ONE packed buffer (solver.rows_layout:
    # the index and the 11 narrow planes of the bucket).
    for rows in canonical_scatter_rows():
        words = sv.rows_layout(sv._cluster_planes(c_abs), rows)[1]
        progs[f"scatter@{rows}"] = (
            "scatter", lambda c, buf, k=rows: raw_scatter(c, buf, k),
            (c_abs, _sds((words,), np.int32)))

    v = CANON["victims"]
    progs["victim_solve"] = ("victim_solve", raw_victim, (
        _sds((n, 4), np.int32), _sds((n, 4), np.int32),
        _sds((n,), np.bool_), _sds((n, v, 4), np.int32),
        _sds((n, v), np.int32), _sds((n, v), np.bool_),
        _sds((4,), np.int32), _sds((), np.bool_),
        _sds((), np.int32)))

    t, d = CANON["topo_terms"], CANON["topo_domains"]
    # topo_dom arrives in the resident mirror's narrow form (int16 at
    # the canonical size) — the topology kernel is the one narrow-plane
    # consumer outside the widening entrypoints, so its manifested aval
    # must match the live dispatch or the first live spread solve would
    # mint an unmanifested shape.
    topo_dtype = ctx.cluster.topo_dom.dtype
    progs["topo_planes"] = ("topo_planes", raw_planes, (
        _sds((t,), np.int32), _sds((t,), np.float32),
        _sds((t,), np.bool_), _sds((t, d), np.float32),
        _sds((t, d), np.bool_), _sds((floor, t), np.bool_),
        _sds((n, 1), topo_dtype)))
    return progs


def manifest_hash(programs: dict) -> str:
    return "sha256:" + hashlib.sha256(
        json.dumps(programs, sort_keys=True).encode()).hexdigest()


def build_manifest(with_jaxprs: bool = False
                   ) -> tuple[dict, dict[str, Any]]:
    """(manifest dict sans justifications, {program key: jaxpr}).

    Pure abstract interpretation: builds the canonical context, traces
    every registered program with ``jax.make_jaxpr`` over
    ShapeDtypeStructs, and assembles the committed JSON's ``programs``
    section.  Runs in a few seconds on any host with jax installed —
    no accelerator, no XLA compile."""
    from kubernetes_tpu.engine import entrypoints
    ctx = build_context()
    families = entrypoints.by_name()
    # Donation state comes from the SOURCE (the jit call's
    # donate_argnums/donate_argnames kwargs): tracing goes through the
    # unjitted ``.__wrapped__`` functions, where donation is invisible,
    # so recording it from the trace would always claim "none".
    donation: dict[str, str] = {
        site.key: site.donate_spec
        for module in engine_modules()
        for site in discover_jit_sites(module) if site.donates}
    programs: dict[str, dict] = {}
    jaxprs: dict[str, Any] = {}
    for key, (family, fn, args) in sorted(program_builders(ctx).items()):
        spec = families[family]
        jaxpr = jax.make_jaxpr(fn)(*args)
        out = jax.eval_shape(fn, *args)
        jaxprs[key] = jaxpr
        programs[key] = {
            "family": family,
            "live_path": spec.live_path,
            "warmed": spec.warmed,
            "dispatch_site": spec.dispatch_site,
            "jit_entrypoints": sorted(spec.jit_entrypoints),
            "in_avals": [_avals(a) for a in args],
            "out_avals": _avals(out),
            "donate_argnums": sorted(
                f"{ep}: {donation[ep]}"
                for ep in spec.jit_entrypoints if ep in donation),
            "fingerprint": jaxpr_fingerprint(jaxpr),
        }
    manifest = {
        "comment": "kt-xray compile-surface manifest — generated by "
                   "`python -m tools.ktxray --write-manifest`; "
                   "tools/check_manifest.py fails tier-1 on drift.",
        "canonical": dict(CANON),
        "jax": jax.__version__,
        "programs": programs,
        "hash": manifest_hash(programs),
    }
    return manifest, jaxprs


# -- X04: coverage ------------------------------------------------------

def _function_exists(module: lint_core.Module, name: str) -> bool:
    return any(isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
               and node.name == name
               for node in ast.walk(module.tree))


def engine_modules(root: str = REPO) -> list[lint_core.Module]:
    paths = [os.path.join(root, p) for p in (
        "kubernetes_tpu/engine", "kubernetes_tpu/engine/workloads")]
    files = sorted(
        os.path.join(d, f) for d in paths if os.path.isdir(d)
        for f in os.listdir(d) if f.endswith(".py"))
    return lint_core.load_project(root, paths=files).modules


def check_x04(programs: dict, modules: list[lint_core.Module]
              ) -> list[XFinding]:
    from kubernetes_tpu.engine import entrypoints
    out: list[XFinding] = []
    # (a) the warmed-program set IS the canonical prewarm plan.
    warmed = sorted(k for k, p in programs.items() if p["warmed"])
    plan = canonical_plan()
    for missing in sorted(set(plan) - set(warmed)):
        out.append(XFinding(
            "X04", missing,
            "prewarm plan program missing from the manifest "
            "(ladder coverage gap)"))
    for extra in sorted(set(warmed) - set(plan)):
        out.append(XFinding(
            "X04", extra,
            "manifest marks this program warmed but Scheduler.prewarm "
            "never traces it (unreachable-from-prewarm signature)"))
    # (b) every AST jit site under engine/ is claimed by a family.
    claimed = entrypoints.claimed_jit_entrypoints()
    discovered: set[str] = set()
    by_path = {m.path: m for m in modules}
    for module in modules:
        for site in discover_jit_sites(module):
            discovered.add(site.key)
    for key in sorted(discovered - claimed):
        out.append(XFinding(
            "X04", key,
            "unmanifested jit entrypoint: no entry in "
            "engine/entrypoints.py claims this jit site"))
    for key in sorted(claimed - discovered):
        out.append(XFinding(
            "X04", key,
            "entrypoints.py claims a jit site the AST scan cannot "
            "find (renamed or deleted function?)"))
    # (c) dispatch sites exist.
    for spec in entrypoints.ENTRYPOINTS:
        path, _, func = spec.dispatch_site.partition(":")
        module = by_path.get(path)
        if module is None:
            module = next((m for m in lint_core.load_project(
                REPO, paths=[os.path.join(REPO, path)]).modules), None) \
                if os.path.exists(os.path.join(REPO, path)) else None
        if module is None or not _function_exists(module, func):
            out.append(XFinding(
                "X04", spec.dispatch_site,
                f"dispatch site for family '{spec.name}' not found"))
    # (d) every manifest program belongs to a registered family.
    families = entrypoints.by_name()
    for key, prog in sorted(programs.items()):
        if prog["family"] not in families:
            out.append(XFinding(
                "X04", key,
                f"program family '{prog['family']}' is not registered "
                f"in engine/entrypoints.py"))
    return out


# -- the check ----------------------------------------------------------

@dataclass
class Result:
    drift: list[str]
    new: list[XFinding]
    justified: list[XFinding]
    stale_justifications: list[str]
    programs: dict

    @property
    def failed(self) -> bool:
        return bool(self.drift or self.new or self.stale_justifications)


def load_manifest(path: str = DEFAULT_MANIFEST) -> Optional[dict]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def manifest_summary(path: str = DEFAULT_MANIFEST) -> Optional[dict]:
    """{'hash', 'programs'} of the COMMITTED manifest (no tracing) —
    bench.py stamps this into BENCH/SOAK artifacts so a compile-surface
    change is visible in the perf trajectory."""
    data = load_manifest(path)
    if data is None:
        return None
    return {"hash": data.get("hash"),
            "programs": len(data.get("programs") or {})}


def diff_programs(committed: dict, rebuilt: dict) -> list[str]:
    drift = []
    for key in sorted(set(committed) - set(rebuilt)):
        drift.append(f"{key}: program vanished from the compile "
                     f"surface (manifest not regenerated)")
    for key in sorted(set(rebuilt) - set(committed)):
        drift.append(f"{key}: new program not in the committed "
                     f"manifest")
    for key in sorted(set(rebuilt) & set(committed)):
        for col in ("fingerprint", "in_avals", "out_avals", "warmed",
                    "dispatch_site", "jit_entrypoints", "family",
                    "donate_argnums"):
            if committed[key].get(col) != rebuilt[key].get(col):
                drift.append(f"{key}: {col} drifted "
                             f"(regenerate the manifest)")
    return drift


def collect_findings(programs: dict, jaxprs: dict[str, Any]
                     ) -> list[XFinding]:
    """Every X01–X04 finding for one rebuilt manifest — the ONE
    collection both ``run_check`` and ``write_manifest`` use, so the
    checker and the regenerator can never disagree about which
    fingerprints need justification."""
    findings: list[XFinding] = []
    for key, jaxpr in sorted(jaxprs.items()):
        findings.extend(check_x01(key, jaxpr))
        findings.extend(check_x02(key, jaxpr))
    modules = engine_modules()
    findings.extend(check_x03(modules))
    findings.extend(check_x04(programs, modules))
    return findings


def run_check(manifest_path: str = DEFAULT_MANIFEST) -> Result:
    """Rebuild the manifest abstractly, diff it against the committed
    file, and run X01–X04; split findings against the committed
    ``justifications`` section (kt-lint's protocol: new findings fail,
    stale justifications fail, drift always fails)."""
    rebuilt, jaxprs = build_manifest()
    committed = load_manifest(manifest_path)
    drift: list[str] = []
    justifications: dict[str, str] = {}
    if committed is None:
        drift.append(f"missing committed manifest {manifest_path} — "
                     f"run `python -m tools.ktxray --write-manifest`")
    else:
        justifications = dict(committed.get("justifications") or {})
        drift.extend(diff_programs(committed.get("programs") or {},
                                   rebuilt["programs"]))
        stored = committed.get("hash")
        expect = manifest_hash(committed.get("programs") or {})
        if stored != expect:
            drift.append("committed manifest hash does not match its "
                         "own programs section (hand-edited?)")
    findings = collect_findings(rebuilt["programs"], jaxprs)
    new = [f for f in findings if f.fingerprint not in justifications]
    seen = {f.fingerprint for f in findings}
    stale = sorted(fp for fp in justifications if fp not in seen)
    return Result(drift=drift, new=new,
                  justified=[f for f in findings
                             if f.fingerprint in justifications],
                  stale_justifications=stale,
                  programs=rebuilt["programs"])


def write_manifest(path: str = DEFAULT_MANIFEST) -> dict:
    """Regenerate the committed manifest, preserving existing
    justification entries whose findings still exist (a regenerate must
    never erase the reasons; stale ones are dropped with the finding)."""
    manifest, jaxprs = build_manifest()
    committed = load_manifest(path)
    old_just = dict((committed or {}).get("justifications") or {})
    findings = collect_findings(manifest["programs"], jaxprs)
    manifest["justifications"] = {
        f.fingerprint: old_just.get(
            f.fingerprint, "JUSTIFY: why this finding is accepted")
        for f in findings}
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.write("\n")
    return manifest
