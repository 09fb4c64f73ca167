"""Central registry of every ``KT_*`` environment knob.

Twelve PRs grew ~50 knobs, each read ad hoc at its own site with its own
default and its own parsing bug surface — the PR 4 ``stream_min_bucket``
incident (a knob re-read after warmup minted unwarmed shapes) is the
canonical failure.  This module is the single source of truth:

* Every knob is **declared** here once — name, default, type, one doc
  line.  ``tools/check_knobs.py`` fails tier-1 when a ``KT_*`` literal
  appears in code but not here, when a declared knob is read nowhere, or
  when the ARCHITECTURE.md "Configuration knobs" table (rendered from
  this registry) drifts.
* Every knob is **read** through :func:`get` / :func:`get_int` /
  :func:`get_float` / :func:`get_bool` — raw ``os.environ`` reads of
  ``KT_*`` names anywhere else are a ktlint D04 finding.  Reading an
  undeclared name raises ``KeyError`` at the call site (a typo'd knob
  must fail loudly in tests, not silently return a default forever).
* All reads follow the daemon-knob contract: unset or
  empty means the default; garbage logs a warning and means the default
  (a mistyped knob must not kill a daemon at startup).
* Reads happen at daemon/object **init**, never per drain — the per-
  drain env read is the D04 hot-path rule, machine-checked by ktlint.

The declared default is authoritative: call sites pass no default unless
the knob's default is site-computed (declared here with ``default=None``
and the derivation in the doc line), in which case the site supplies it
via the ``default=`` override.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Optional

# stdlib logging directly: utils/logging.py itself reads KT_LOG_V
# through this registry, so this module must sit below it.
log = logging.getLogger("kubernetes_tpu.knobs")


@dataclass(frozen=True)
class Knob:
    name: str
    default: Optional[str]  # None = site-computed (see doc line)
    kind: str               # "int" | "float" | "str" | "bool"
    doc: str


REGISTRY: dict[str, Knob] = {}


def _knob(name: str, default: Optional[str], kind: str, doc: str) -> Knob:
    k = Knob(name, default, kind, doc)
    REGISTRY[name] = k
    return k


# -- observability ------------------------------------------------------
_knob("KT_TRACE", "1", "bool",
      "Span tracer on/off; 0 disables all span recording (one branch)")
_knob("KT_TRACE_RING", "8192", "int",
      "Trace ring capacity in spans (lazily allocated)")
_knob("KT_TRACE_SAMPLE", "1", "float",
      "Root-span sampling fraction 0.0-1.0; children follow the root")
_knob("KT_LOG_V", "0", "int",
      "Log verbosity (glog -v shape): <=1 INFO, <5 DEBUG, >=5 VERBOSE")
_knob("KT_PROFILE_DIR", "", "str",
      "Default of the daemon's --profile-dir: where /debug/pprof/trace "
      "writes its windowed traces (empty = a temp dir per trace)")
_knob("KT_TELEMETRY_RING", "720", "int",
      "Self-scrape time-series ring capacity in samples")
_knob("KT_TELEMETRY_PERIOD", "5", "float",
      "Self-scrape cadence in seconds (0 = no sampler thread)")
_knob("KT_PROF", "1", "bool",
      "kt-prof continuous CPU profiler; 0 = off (one branch, no sampler "
      "thread, /debug/profile answers 404)")
_knob("KT_PROF_HZ", "19", "float",
      "kt-prof max sample rate in Hz (off-beat default so the sampler "
      "never phase-locks with periodic work; the loop self-paces below "
      "this to keep sampler CPU under 2%)")
_knob("KT_PROF_RING", "512", "int",
      "kt-prof folded-stack table bound (distinct stacks; overflow CPU "
      "folds into one ring-truncated bucket)")
# -- engine / device ----------------------------------------------------
_knob("KT_PREWARM", "0", "bool",
      "Trace the bucket ladder before the queue opens (perf rigs, prod)")
_knob("KT_DYN_TEMPLATES", "64", "int",
      "Max distinct nonzero-request templates factored out of the scan "
      "body; batches above it keep the in-scan score path")
_knob("KT_PREEMPT_MAX_VICTIMS", "16", "int",
      "Victim-table depth per node for the preemption solve")
_knob("KT_STREAM_CHUNK", "0", "int",
      "Stream-path chunk size; 0 = one-shot solves only")
_knob("KT_STREAM_MIN_BUCKET", None, "int",
      "Smallest pow2 drain bucket (default Scheduler.STREAM_MIN_BUCKET); "
      "read ONCE at daemon startup")
_knob("KT_GUARD", "1", "bool",
      "Guarded device execution (engine/guard.py); 0 = raw solves")
_knob("KT_GUARD_BREAKER", "3", "int",
      "Consecutive same-kind device faults before the host breaker trips")
_knob("KT_GUARD_PROBE_S", "15", "float",
      "Seconds between device probe solves while the breaker is open")
_knob("KT_GUARD_ROUNDS", "6", "int",
      "Bound on guard recovery rounds per drain")
_knob("KT_GUARD_CAP_RESET", "4", "int",
      "Device-healthy drains before a bisected bucket cap lifts")
_knob("KT_HBM_WATERMARK", "0", "float",
      "Proactive HBM ceiling in bytes (0 = off): past it, cap at the "
      "ladder floor + evict before the allocator throws")
_knob("KT_CHAOS_DEVICE", "", "str",
      "Accelerator fault-injection spec, e.g. 'oom@7,lost@50:1' "
      "(chaos/device.py)")
# -- scheduler daemon ---------------------------------------------------
_knob("KT_RECOVERY", "1", "bool",
      "Startup cache/queue reconciliation against one apiserver relist")
_knob("KT_PIPELINE_WINDOW", "2", "int",
      "Overlapped solve/bind in-flight chunk window (0 = synchronous)")
_knob("KT_BATCH_DEADLINE_MS", "", "float",
      "Deadline micro-batching window in ms (empty/0 = off)")
_knob("KT_QUEUE_HIGH_WATERMARK", "65536", "int",
      "Queue depth past which drains degrade to bounded pops (0 = off)")
_knob("KT_POD_BACKOFF_S", "1", "float",
      "Initial per-pod requeue backoff in seconds")
_knob("KT_POD_BACKOFF_MAX_S", "60", "float",
      "Per-pod requeue backoff ceiling in seconds")
_knob("KT_BIND_PIPELINE", "4", "int",
      "Persistent connections pipelining bind-chunk POSTs")
_knob("KT_AIMD_MIN", "1", "int",
      "AIMD bind fan-out concurrency floor (ceiling is "
      "KT_BIND_PIPELINE)")
_knob("KT_AIMD_BACKOFF", "0.5", "float",
      "AIMD multiplicative-decrease factor applied on a server 429")
_knob("KT_FLIGHT_DIR", "", "str",
      "Directory persisting the decision flight ring across restarts")
_knob("KT_VERIFY_PERIOD", "0", "float",
      "Resident-state invariant checker cadence in seconds (0 = off)")
_knob("KT_SLO_PERIOD", "5", "float",
      "SLO burn monitor tick cadence in seconds (0 = off)")
_knob("KT_SLO_MS", "1000", "float",
      "Decision-latency SLO threshold in ms")
_knob("KT_SLO_OBJECTIVE", "99", "float",
      "SLO objective in percent of decisions inside KT_SLO_MS")
# -- apiserver ----------------------------------------------------------
_knob("KT_BIND_CAPACITY", "1", "bool",
      "Server-side bind capacity validation (overcommit binds 409)")
_knob("KT_APF", "1", "bool",
      "APF-style priority-level flow control in the apiserver request "
      "loop; 0 = admit everything (pre-PR-16 behavior)")
_knob("KT_APF_SYSTEM_INFLIGHT", "16", "int",
      "Reserved max-inflight slots for the system level (lease/presence "
      "CAS, heartbeats); never queued, never starved by lower levels")
_knob("KT_APF_WORKLOAD_INFLIGHT", "32", "int",
      "Max-inflight for the workload level (binds, evictions, solve "
      "traffic)")
_knob("KT_APF_BESTEFFORT_INFLIGHT", "16", "int",
      "Max-inflight for the best-effort level (pod-create storms, LISTs)")
_knob("KT_APF_QUEUE", "64", "int",
      "Bounded FIFO wait-queue depth per queueable level; a full queue "
      "sheds 429 + Retry-After")
_knob("KT_APF_QUEUE_WAIT_S", "1.0", "float",
      "Queue wait deadline in seconds; past it the request sheds 429")
_knob("KT_APF_WATCH_INFLIGHT", "128", "int",
      "Concurrent watch-stream cap; watches are admitted or 429d, "
      "never queued (a stream holds its handler thread for its life)")
_knob("KT_APF_RETRY_AFTER_S", "0.25", "float",
      "Floor of the honest Retry-After hint on shed responses")
_knob("KT_NATIVE_APISERVER", "1", "bool",
      "Perf rigs use the native apiserver binary when available")
_knob("KT_WATCH_FRAMES", "1", "bool",
      "Clients request the framed (length-prefixed multi-event) watch "
      "encoding; 0 = per-event NDJSON lines")
# -- active-active HA ---------------------------------------------------
_knob("KT_HA_SHARDS", "0", "int",
      "Namespace-hash shard count; >0 enables active-active HA")
_knob("KT_INCARNATION", "", "str",
      "Stable incarnation identity (default: random scheduler-<hex>)")
_knob("KT_HA_LEASE_S", "3.0", "float",
      "Shard lease duration in seconds")
_knob("KT_HA_RENEW_S", None, "float",
      "Lease renew deadline (default KT_HA_LEASE_S * 2/3)")
_knob("KT_HA_RETRY_S", None, "float",
      "Lease acquisition retry period (default KT_HA_LEASE_S / 6)")
_knob("KT_HA_SWEEP_S", "10", "float",
      "Periodic ownership-sweep reconcile cadence in seconds (0 = off)")
_knob("KT_HA_STALE_ASSUME_S", "3", "float",
      "Sweep-side assume age past any healthy bind round-trip")
# -- multi-tenant solver service ----------------------------------------
_knob("KT_TENANTS", "", "str",
      "Comma-separated tenant set; non-empty embeds the SolverService")
_knob("KT_TENANT_WEIGHTS", "", "str",
      "Weighted shares, 't-a:3,t-b:1' (default 1.0 each)")
_knob("KT_TENANT_BREAKER", "2", "int",
      "Consecutive per-tenant faults before that tenant degrades to host")
_knob("KT_TENANT_PROBE_S", "10", "float",
      "Per-tenant device probe cadence while degraded")
_knob("KT_TENANT_PACK_MS", "5", "float",
      "Packed-submit coalescing window in ms")
_knob("KT_TENANT_URGENT_MS", "", "float",
      "Urgency-lane queue-age override in ms (default: the formation "
      "deadline)")
# -- perf rigs / tests --------------------------------------------------
_knob("KT_WIRE_CHUNK", None, "int",
      "density_wire stream chunk (default: 4096, pipelined; smaller "
      "queues round up to a multiple of 2048)")
_knob("KT_WIRE_ACCUM", "20", "float",
      "density_wire batch-formation deadline in ms")
_knob("KT_PERF_ASSERTS", "1", "bool",
      "Wall-clock assertions in perf-sensitive tests (0 on slow rigs)")
# -- continuous rebalancing (ISSUE 17) ----------------------------------
_knob("KT_DEFRAG", "0", "bool",
      "Background defragmentation loop (scheduler/defrag.py): dry joint "
      "solves over the bound state propose bounded migration batches")
_knob("KT_DEFRAG_PERIOD_S", "30", "float",
      "Defrag round cadence in seconds (a round = settle in-flight "
      "migrations, probe-solve the blocked set, plan + execute a batch)")
_knob("KT_DEFRAG_MAX_MIGRATIONS", "8", "int",
      "Hard cap on migrations executed per defrag round (window); a "
      "plan is trimmed to it before the gain gate")
_knob("KT_DEFRAG_MIN_GAIN", "0.5", "float",
      "Cost-model floor: projected placements unblocked per migration; "
      "a batch below it is vetoed (recorded vetoed-by-budget)")
_knob("KT_DEFRAG_BUDGET", "16", "int",
      "Disruption budget: max evicted-but-not-yet-rebound pods allowed "
      "in flight at once; new batches are vetoed while it is spent")
# -- concurrency discipline (ISSUE 13) ----------------------------------
_knob("KT_LOCKTRACE", "0", "bool",
      "Instrumented locks: per-thread acquisition chains, order-"
      "inversion + long-hold detection (utils/locktrace.py)")
_knob("KT_LOCKTRACE_HOLD_MS", "100", "float",
      "Lock hold duration past which locktrace records a long-hold")


def _declared(name: str) -> Knob:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"{name} is not a declared knob — add it to "
            f"kubernetes_tpu/utils/knobs.py (tools/check_knobs.py "
            f"ratchets the registry)") from None


def get(name: str, default: Optional[str] = None) -> str:
    """The raw string value: environment, else the (site-overridable)
    declared default, else ''."""
    knob = _declared(name)
    raw = os.environ.get(name)
    if raw is not None and raw.strip():
        return raw.strip()
    if default is not None:
        return default
    return knob.default or ""


def get_str(name: str, default: Optional[str] = None) -> str:
    return get(name, default)


def get_int(name: str, default: Optional[int] = None) -> int:
    raw = get(name, None if default is None else str(default))
    try:
        # int("3.0") raises; the float round-trip keeps e.g.
        # KT_HBM_WATERMARK=2e9 working as an integer byte count.
        return int(float(raw)) if raw else 0
    except ValueError:
        fallback = default if default is not None \
            else int(float(_declared(name).default or "0") or 0)
        log.warning("bad %s=%r; using %s", name, raw, fallback)
        return fallback


def get_float(name: str, default: Optional[float] = None) -> float:
    raw = get(name, None if default is None else str(default))
    try:
        return float(raw) if raw else 0.0
    except ValueError:
        fallback = default if default is not None \
            else float(_declared(name).default or "0" or 0.0)
        log.warning("bad %s=%r; using %s", name, raw, fallback)
        return fallback


def get_bool(name: str, default: Optional[bool] = None) -> bool:
    """The flag contract every existing bool knob follows: unset means
    the declared default; set-but-empty or '0' means off; anything else
    means on."""
    knob = _declared(name)
    raw = os.environ.get(name)
    if raw is None:
        if default is not None:
            return default
        raw = knob.default or "0"
    return raw not in ("", "0")


def render_table() -> str:
    """The ARCHITECTURE.md "Configuration knobs" table, rendered from
    the registry (tools/check_knobs.py --render; the check fails tier-1
    when the committed table drifts from this output)."""
    lines = ["| Knob | Default | Type | Purpose |",
             "| --- | --- | --- | --- |"]
    for name in sorted(REGISTRY):
        k = REGISTRY[name]
        default = "(site-computed)" if k.default is None else \
            (f"`{k.default}`" if k.default else "(empty)")
        lines.append(f"| `{k.name}` | {default} | {k.kind} | {k.doc} |")
    return "\n".join(lines) + "\n"
