"""Score combination and host selection.

The reference sums each priority's 0-10 score times its integer weight per
node (PrioritizeNodes, generic_scheduler.go:233-314) and then picks among the
top-scoring nodes round-robin (selectHost, generic_scheduler.go:124-141).

Here the combine is a single weighted contraction over stacked score planes,
and selectHost is vectorized over the pod batch: pod ``i`` in the batch takes
the ``(last_node_index + i) mod ties``-th feasible argmax node, reproducing
the serial counter semantics.  The reference's tie *order* is nondeterministic
(Go map iteration feeding an unstable sort), so parity is defined as "chosen
node is in the reference's argmax set"; we fix node-index order to make our
own output deterministic.

``select_host`` is the same rule for ONE pod's row, as the sequential
scan's step applies it (engine/solver.py ``_solve_scan``): the per-step
mask -> score -> tie-break -> select chain is the floor of the scan's
cost once the score planes are template-factored, so it is arranged as
three node-axis reductions for XLA's fuser.
"""

from __future__ import annotations

import jax.numpy as jnp


def combine_scores(score_planes: jnp.ndarray,
                   weights: jnp.ndarray) -> jnp.ndarray:
    """[K,P,N] score planes x [K] int weights -> [P,N] f32 combined."""
    return jnp.einsum("kpn,k->pn", score_planes, weights.astype(jnp.float32))


def select_hosts(scores: jnp.ndarray, feasible: jnp.ndarray,
                 last_node_index: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized selectHost.

    Args:
      scores: [P,N] f32 combined scores.
      feasible: [P,N] bool predicate mask.
      last_node_index: scalar uint32 round-robin counter (g.lastNodeIndex).

    Returns:
      (choice [P] int32 — node index or -1 if no feasible node,
       new_last_node_index scalar).
    """
    neg = jnp.float32(-jnp.inf)
    masked = jnp.where(feasible, scores, neg)
    max_score = jnp.max(masked, axis=1, keepdims=True)  # [P,1]
    any_feasible = jnp.any(feasible, axis=1)  # [P]
    ties = feasible & (masked == max_score)  # [P,N]
    n_ties = jnp.maximum(jnp.sum(ties, axis=1), 1)  # [P]
    # Serial counter semantics: lastNodeIndex only advances inside selectHost
    # (generic_scheduler.go:135-137), which unschedulable pods never reach —
    # so pod i's counter read skips earlier infeasible pods.
    feas_before = jnp.cumsum(any_feasible.astype(jnp.uint32)) - \
        any_feasible.astype(jnp.uint32)  # [P]
    counter = (last_node_index + feas_before) % n_ties.astype(jnp.uint32)
    rank = jnp.cumsum(ties.astype(jnp.int32), axis=1) - 1  # [P,N]
    pick = ties & (rank == counter[:, None].astype(jnp.int32))
    choice = jnp.argmax(pick, axis=1).astype(jnp.int32)
    choice = jnp.where(any_feasible, choice, -1)
    return choice, last_node_index + jnp.sum(any_feasible.astype(jnp.uint32))


def select_host(masked: jnp.ndarray, counter: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """selectHost for one pod: (choice int32 [-1 = infeasible],
    any_feasible bool).

    ``masked`` [N] f32 with -inf at infeasible nodes (the caller folds
    the static mask and the dynamic predicate results into the score
    row, so the row is the whole decision input); ``counter`` uint32
    round-robin state.  Among the max-score nodes, the
    ``counter % n_ties``-th in node-index order.  Three node-axis
    passes: max, cumsum (whose last element is the tie count — no
    separate sum pass), argmax.  The round-robin modulo runs in uint32:
    an int32 cast would go negative past 2^31 cumulative placements and
    the negative remainder would mark every pod unschedulable."""
    mx = jnp.max(masked)
    ties = (masked == mx) & jnp.isfinite(mx)
    rank = jnp.cumsum(ties.astype(jnp.int32))  # 1-based among ties
    n_raw = rank[-1]
    any_feasible = n_raw > 0
    ix = (counter % jnp.maximum(n_raw, 1).astype(jnp.uint32)) \
        .astype(jnp.int32)
    choice = jnp.argmax(ties & (rank == ix + 1)).astype(jnp.int32)
    return jnp.where(any_feasible, choice, -1), any_feasible
