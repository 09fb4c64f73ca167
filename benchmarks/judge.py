"""What decides ``correct``: the window's answers against the
configuration's plain reference (``reference.py``, or the
``references/<word>.py`` it names; the interface is in ``reference.py``'s
docstring) and the configuration's guarantees.

Everything is read from the client's side — the observer's record of
binds and deletes in watch (resourceVersion) order, the acknowledged
creates, the apiserver's own list at close — plus the daemon's account of
how it solved.  Run after the window has closed and the daemon has been
stopped.

Exact numbers (limit 0), over EVERY pod of the run:
  lost_pods, never_bound, double_binds, unknown_node_binds, each name of
  the reference's ``GUARANTEES`` (``reference.py``: selector_violations,
  over_allocatable; asked of ``broken`` at every bind of the record
  before it is added, and again of every pod in the list at close),
  list_mismatch, client_errors, and the account's counters.

Decision numbers, over a seed-drawn sample of the binds seen inside the
window: ``gap`` = reference's best score among fitting nodes minus the
score of the node the program chose, in score points, on the cluster
state the program can have seen.  The client cannot know how many of its
deletes the daemon had ingested when it took a launch's snapshot, only
that they are ingested in order and that the snapshot is no older than
``judge.max_lag_s`` seconds (the configuration states it: a launch in
flight plus one stall of the daemon), so the state is "every earlier
bind, and all but the last L deletes", L = 0, step, 2*step, ...
(``judge.lag_step``) for as long as the L-th last delete was seen within
``max_lag_s`` before the bind, and the gap is the least over those L.  A
wrong choice stays wrong for every L; a right one reads 0 at the right L,
or close to it at the nearest step.  ``gap_mean`` and ``gap_max`` carry
the configuration's limits; ``infeasible_choices`` (the chosen node does
not fit at any L) has the limit 0.  ``gap0_mean`` (no lag searched at
all) is printed beside them, never judged.
"""

from __future__ import annotations

import bisect

import numpy as np

import rig
from loadgen import BIND


def _bind(ref, state, counts: dict, pod: int, node: int) -> None:
    """Ask the reference which guarantees the bind breaks, then add it."""
    for name, value in ref.broken(state, pod, node).items():
        counts[name] += value
    state.add(pod, node)


def replay(ref, nodes, pods, events: list, sample_at: set, judge_cfg: dict
           ) -> dict:
    """One pass over the observer's record, the cluster kept in the
    configuration's reference ``State`` throughout.  Each name of
    ``ref.GUARANTEES`` is counted over EVERY bind, asked before the bind
    is added."""
    n = nodes.n
    state = ref.State(nodes, pods)
    node_of: dict = {}
    deleted: list = []
    deleted_t: list = []
    out = {"double_binds": 0, "unknown_node_binds": 0,
           "deletes_of_unbound": 0}
    out.update({name: 0 for name in ref.GUARANTEES})
    gaps, gaps0, lags = [], [], []
    step, max_lag_s = int(judge_cfg["lag_step"]), float(judge_cfg["max_lag_s"])
    for at, (kind, pod, node, t) in enumerate(events):
        if kind == BIND:
            if pod in node_of:
                out["double_binds"] += 1
                continue
            if not 0 <= node < n:
                out["unknown_node_binds"] += 1
                continue
            if at in sample_at:
                # deletes seen within max_lag_s before this bind
                young = len(deleted) - bisect.bisect_left(
                    deleted_t, t - max_lag_s)
                gap, gap0, lag = _gap(ref, state, deleted, node_of, pod,
                                      node, step, young)
                gaps.append(gap)
                gaps0.append(gap0)
                lags.append(lag)
            node_of[pod] = node
            _bind(ref, state, out, pod, node)
        else:
            where = node_of.get(pod)
            if where is None or where < 0:
                out["deletes_of_unbound"] += 1
                continue
            state.add(pod, where, -1)
            node_of[pod] = -1 - where      # gone; remembers where it was
            deleted.append(pod)
            deleted_t.append(t)
    finite = [g for g in gaps if g != float("inf")]
    out["sampled_decisions"] = len(gaps)
    out["infeasible_choices"] = len(gaps) - len(finite)
    out["gap_mean"] = float(np.mean(finite)) if finite else 0.0
    out["gap_max"] = float(max(finite)) if finite else 0.0
    finite0 = [g for g in gaps0 if g != float("inf")]
    out["gap0_mean"] = float(np.mean(finite0)) if finite0 else 0.0
    out["lag_deletes_median"] = float(np.median(lags)) if lags else 0.0
    out["lag_deletes_max"] = float(max(lags)) if lags else 0.0
    out["node_of"] = node_of
    return out


def _gap(ref, state, deleted, node_of, pod, node, step, young
         ) -> tuple[float, float, int]:
    """``(least gap, gap with no lag, lag of the least)``; ``young`` = how
    many of the last deletes the program may not have seen yet.  The
    search puts them back, newest first, into a copy of ``state``."""
    gap0 = ref.score_gap(state, pod, node)
    if gap0 == 0 or young <= 0:
        return gap0, gap0, 0
    best, best_lag = gap0, 0
    hi = len(deleted)
    oldest = hi - young
    state = state.copy()
    while best != 0 and hi > oldest:
        lo = max(hi - step, oldest)
        for back in deleted[lo:hi]:
            state.add(back, -1 - node_of[back])
        hi = lo
        gap = ref.score_gap(state, pod, node)
        if gap < best:
            best, best_lag = gap, len(deleted) - hi
    return best, gap0, best_lag


def judge(ref, nodes, pods, book, n_offered: int, final_list: dict,
          window: tuple, seed: int, config: dict, account: dict,
          platform: str) -> tuple[bool, dict, dict]:
    """``(correct, {name: [number, limit]}, info)``.  ``ref`` = the
    configuration's reference module (``run.parts_of``); ``n_offered`` =
    pods the generator wrote to the apiserver; ``final_list`` = the
    apiserver's ``{pod: node or -1}`` at close."""
    events = book.events
    t_open, t_close = window
    in_window = [i for i, (kind, _p, _n, t) in enumerate(events)
                 if kind == BIND and t_open <= t < t_close]
    want = int(config["judge"]["sample"])
    rng = np.random.RandomState((seed + 3) % (2 ** 32))
    if len(in_window) > want:
        in_window = rng.choice(in_window, want, replace=False).tolist()
    rep = replay(ref, nodes, pods, events, set(in_window), config["judge"])
    node_of = rep.pop("node_of")

    # every acknowledged create: bound (resident or retired), never lost
    lost = never_bound = mismatch = 0
    for pod in range(book.n_created):
        where = node_of.get(pod)
        listed = final_list.get(pod)
        if where is None:
            if listed is None:
                lost += 1
            elif listed == -1:
                never_bound += 1
            else:
                mismatch += 1
        elif where >= 0 and listed != where:
            mismatch += 1
        elif where < 0 and listed is not None:
            mismatch += 1
    mismatch += sum(1 for pod in final_list
                    if pod < 0 or pod >= max(n_offered, book.n_created))
    # the list at close, recomputed through the same State: every pod
    # it holds asked again, in name order, whatever the record said
    used = ref.State(nodes, pods)
    at_close = {name: 0 for name in ref.GUARANTEES}
    for pod, node in sorted(final_list.items()):
        if 0 <= node < nodes.n and 0 <= pod < len(pods):
            _bind(ref, used, at_close, pod, node)

    limits = config["limits"]
    numbers = {
        "gap_mean": [rep["gap_mean"], limits["gap_mean"]],
        "gap_max": [rep["gap_max"], limits["gap_max"]],
        "infeasible_choices": [rep["infeasible_choices"], 0],
        "lost_pods": [lost, 0],
        "never_bound": [never_bound, 0],
        "double_binds": [rep["double_binds"], 0],
        "unknown_node_binds": [rep["unknown_node_binds"], 0],
        **{name: [rep[name] + at_close[name], 0]
           for name in ref.GUARANTEES},
        "list_mismatch": [mismatch + rep["deletes_of_unbound"], 0],
        "client_errors": [len(book.errors), 0],
        "engine_not_device": [int(account["mode"] != "device"
                                  or account["platform"] != platform
                                  or account["last_fault"] is not None), 0],
        "host_mode_seconds": [account["host_mode_seconds"], 0],
        "invariant_violations": [account["invariant_violations"], 0],
    }
    for family in rig.ACCOUNT_FAMILIES:
        short = family[len("scheduler_"):-len("_total")]
        numbers[short] = [account[family], 0]
    if rep["sampled_decisions"] == 0:
        numbers["sampled_decisions_missing"] = [1, 0]
    correct = all(value <= limit for value, limit in numbers.values())
    info = {"sampled_decisions": rep["sampled_decisions"],
            "gap0_mean": rep["gap0_mean"],
            "lag_deletes_median": rep["lag_deletes_median"],
            "lag_deletes_max": rep["lag_deletes_max"]}
    return correct, numbers, info
