"""The jitted-entrypoint registry: every live-path XLA program, named.

PAPER.md calls the JAX-generated XLA/Pallas kernels this system's
"native layer"; this module is that layer's table of contents.  Each
entry names one compiled program family the runtime can dispatch — the
jit entrypoint(s) it compiles through, the runtime dispatch site that
launches it, and the live-path label the PR 9 recompile watchdog files
its compiles under.

Consumers:

* ``kubernetes_tpu/analysis/xray.py`` abstractly traces every entry via
  ``jax.eval_shape`` / ``jax.make_jaxpr`` (no device, no compile) into
  the committed ``tools/shape_manifest.json`` and proves the X-rules
  over the jaxprs;
* rule X04 cross-checks this registry three ways: every AST-discovered
  jit site under ``engine/`` must be claimed by some entry (an
  unregistered jit entrypoint is an unmanifested compile surface),
  every entry's dispatch site must exist, and the manifest's warmed
  programs must equal ``scheduler.prewarm_plan``'s canonical plan.

Adding a jitted function to the engine without registering it here
fails tier-1 — by design: a new compile surface must be manifested
(and prewarmed) before it can ship.
"""

from __future__ import annotations

from typing import NamedTuple


class EntrySpec(NamedTuple):
    """One live-path program family.

    ``name``: program-family name; manifest program keys are either the
    bare name or ``name@<pod bucket>``.
    ``live_path``: the ``devicestats.live_path`` label its dispatch
    site runs under ("" = launched outside a watchdog-labelled region,
    e.g. the single-pod failure-detail masks pass).
    ``jit_entrypoints``: ``"<repo-relative path>:<function>"`` of each
    jit/pjit site this family compiles through.
    ``dispatch_site``: ``"<repo-relative path>:<function>"`` of the
    runtime function that launches it.
    ``warmed``: traced by ``Scheduler.prewarm()`` (X04 pins the warmed
    set against ``scheduler.prewarm_plan``).
    """

    name: str
    live_path: str
    jit_entrypoints: tuple[str, ...]
    dispatch_site: str
    warmed: bool
    doc: str


_SOLVER = "kubernetes_tpu/engine/solver.py"
_GS = "kubernetes_tpu/engine/generic_scheduler.py"
_PRE = "kubernetes_tpu/engine/workloads/preemption.py"
_TOPO = "kubernetes_tpu/engine/workloads/topology.py"

ENTRYPOINTS: tuple[EntrySpec, ...] = (
    EntrySpec(
        "scan_first", "stream", (f"{_SOLVER}:_solve_scan",),
        f"{_GS}:schedule_batch_stream", True,
        "First stream chunk / one-shot sequential solve: the scan with "
        "no carried state, live-mask padded to a ladder bucket "
        "(packed aggregates, template score planes, one select per "
        "step)."),
    EntrySpec(
        "scan_carry", "stream", (f"{_SOLVER}:_solve_scan",),
        f"{_GS}:schedule_batch_stream", True,
        "Later stream chunks: the same scan continuing the previous "
        "chunk's carried (donated) state."),
    EntrySpec(
        "oneshot_topo", "oneshot", (f"{_SOLVER}:_solve_scan",),
        f"{_GS}:schedule_batch", True,
        "The workload-constrained one-shot solve: extra_mask + "
        "score_bias planes (topology spread) enter the scan at the "
        "floor bucket (gang drains pad onto the same signatures)."),
    EntrySpec(
        "joint", "joint",
        (f"{_SOLVER}:_solve_joint_jit", f"{_SOLVER}:_price_iterate"),
        f"{_GS}:schedule_batch", True,
        "The LP-relaxed joint assignment: price iteration + regret "
        "ordering + repair scan as one executable."),
    EntrySpec(
        "single_evaluate", "single_pod", (f"{_SOLVER}:evaluate",),
        f"{_GS}:_schedule_device", True,
        "The single-pod decision path's feasibility/score evaluation "
        "(schedule_one, recovery parity probes)."),
    EntrySpec(
        "single_masks", "", (f"{_SOLVER}:masks",),
        f"{_GS}:_schedule_device", False,
        "Per-predicate masks for FitError detail — the single-pod "
        "failure branch plus explain_failures/preemption masks passes; "
        "launched outside the live-path clock, so prewarm does not "
        "trace it (X04 tracks it as a manifested, unwarmed surface)."),
    EntrySpec(
        "select_hosts", "single_pod", (),
        f"{_GS}:_schedule_device", True,
        "Vectorized selectHost (ops/combine.py) — eager jnp ops, not a "
        "jit site, but still a compiled live-path program; prewarm's "
        "single-pod trace covers it."),
    EntrySpec(
        "scatter", "stream", (f"{_SOLVER}:_scatter_fn",),
        f"{_SOLVER}:sync", True,
        "The dirty-row scatter kernel of the device-resident mirror, "
        "compiled per pow2 dirty-row bucket "
        "(ResidentCluster.scatter_buckets)."),
    EntrySpec(
        "victim_solve", "victim", (f"{_PRE}:victim_solve",),
        f"{_GS}:_find_preemptions_inner", True,
        "The vmapped minimal-victim-prefix kernel of priority "
        "preemption."),
    EntrySpec(
        "topo_planes", "oneshot", (f"{_TOPO}:_planes_kernel",),
        f"{_TOPO}:spread_planes", True,
        "Topology-spread hard-mask/soft-score planes contracted "
        "against the cluster topology tensor."),
)


def by_name() -> dict[str, EntrySpec]:
    return {e.name: e for e in ENTRYPOINTS}


def claimed_jit_entrypoints() -> set[str]:
    """Every ``path:function`` some registered family compiles
    through — X04's 'no unmanifested jit entrypoints' universe."""
    out: set[str] = set()
    for e in ENTRYPOINTS:
        out.update(e.jit_entrypoints)
    return out
