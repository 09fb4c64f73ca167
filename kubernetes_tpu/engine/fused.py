"""Fused selectHost for the solve scan's inner step.

The per-step mask -> score -> tie-break -> select chain is the floor of
the sequential solve's cost once the score planes are template-factored
(engine/solver.py ``_solve_scan``): four reduction passes over the node
axis per pod.  ``select_xla`` provides that chain as ONE unit of jnp ops
arranged for XLA's fuser (three reductions: max, one cumsum that also
yields the tie count, argmax) — the one select on every backend.

Semantics (generic_scheduler.go:124-141 selectHost): among the feasible
max-score nodes, pick the ``counter % n_ties``-th in node-index order;
``-1`` when nothing is feasible.  ``masked`` already encodes
infeasibility as ``-inf`` (the caller folds the static mask and the
dynamic predicate results into the score plane), so a single row is the
whole per-pod decision input.
"""

from __future__ import annotations

import jax.numpy as jnp


def select_xla(masked: jnp.ndarray, counter: jnp.ndarray
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(choice int32 [-1 = infeasible], any_feasible bool) for one pod.

    ``masked`` [N] f32 with -inf at infeasible nodes; ``counter`` uint32
    round-robin state.  Three node-axis passes: max, cumsum (whose last
    element is the tie count — no separate sum pass), argmax.  The
    round-robin modulo runs in uint32: an int32 cast would go negative
    past 2^31 cumulative placements and the negative remainder would
    mark every pod unschedulable."""
    mx = jnp.max(masked)
    ties = (masked == mx) & jnp.isfinite(mx)
    rank = jnp.cumsum(ties.astype(jnp.int32))  # 1-based among ties
    n_raw = rank[-1]
    any_feasible = n_raw > 0
    ix = (counter % jnp.maximum(n_raw, 1).astype(jnp.uint32)) \
        .astype(jnp.int32)
    choice = jnp.argmax(ties & (rank == ix + 1)).astype(jnp.int32)
    return jnp.where(any_feasible, choice, -1), any_feasible
