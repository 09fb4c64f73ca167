#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

  python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Measures the served path — client -> native apiserver -> watch ->
queue/former -> feature build -> scatter -> device scan -> readback ->
assume -> bind — from the client's side, with the apiserver, the
scheduler daemon (the only process that touches JAX) and the load
generator (threads of this process) each in a process of its own.

Phases, on this process's monotonic clock:

  set-up   build + start the apiserver, create the nodes, start the
           daemon, wait for its prewarm, then RAMP: start the cell's
           traffic and run it until its steady state holds (the resident
           bound population at the configuration's cap, retirements
           flowing) for ``SETTLE_S``.            -> ``setup_s``
  window   exactly ``--seconds`` of steady state.  A rate is every bind
           seen in the window over ``--seconds``; a latency percentile is
           over every pod whose create was due in the window.  With
           ``--trace 1`` a 2 s profiler session follows it, traffic unchanged.
  close    stop creating, wait (bounded) until every acknowledged create
           is bound, read the daemon's account and device memory, stop
           daemon and apiserver, judge ``correct`` against the plain
           reference, reduce the trace (``--trace 1``), print the line.

This process never imports JAX.  Without a TPU the daemon fails at
start-up (``JAX_PLATFORMS=tpu``) and the run exits non-zero with no
result line.  Everything but the last stdout line goes to stderr or
under ``benchmarks/out/``.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cluster  # noqa: E402
import judge as judging  # noqa: E402
import reference  # noqa: E402
import rig  # noqa: E402

SETTLE_S = 3.0            # steady state has to hold this long before the window
RAMP_TIMEOUT_S = 150.0    # a cold cache compiles every ramp burst's launch size (~65 s)
DRAIN_TIMEOUT_S = 60.0    # an answer may come a minute late; later is never
TRACE_SPAN_S = 2.0        # traced right after the window, traffic unchanged


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T0:7.2f}] {msg}", file=sys.stderr,
          flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise rig.RunFailure(f"no {directory}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def parts_of(config: dict) -> tuple:
    """``(shapes, reference)`` modules of a configuration: the files its
    optional keys ``"shapes"`` / ``"reference"`` name
    (``shapes/<word>.py``, ``references/<word>.py``; interfaces in
    ``cluster.py``'s and ``reference.py``'s docstrings), else
    ``cluster.py`` and ``reference.py``."""
    return (load_module("shapes", config["shapes"])
            if "shapes" in config else cluster,
            load_module("references", config["reference"])
            if "reference" in config else reference)


class Cell:
    """A workload of BENCHMARK.json with its files loaded."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise rig.RunFailure(f"BENCHMARK.json has no workload {name!r}; "
                                 f"it has {sorted(cells)}")
        self.bench = bench
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(rig.REPO, cfg["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        self.kind = load_module("generators", self.traffic["kind"])

    def reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self) -> list:
        """The cell's per-layer metrics, each with its reader file."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if self.reports(m) and m["moves"] in e2e:
                spec = load_json(os.path.join(HERE, "metrics",
                                              m["name"] + ".json"))
                out.append((m, spec))
        return out


def out_dir_of(cell_name: str, seed: int, trace: bool) -> str:
    """Where a run keeps its children's logs, ``info.json`` (what else it
    saw) and ``record.npz``."""
    return os.path.join(HERE, "out", f"{cell_name}.{seed}.{int(trace)}")


def _percent(part: float, whole: float) -> float:
    return 100.0 * part / whole if whole > 0 else 0.0


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             platform: str = "tpu", make_sut=None) -> dict:
    """Drive one run and return the result object.  ``platform`` is what
    the daemon is pinned to and has to report (``main`` always passes
    ``"tpu"``); ``make_sut`` lets the benchmark's own tests put another
    system under test in the daemon's place."""
    out_dir = out_dir_of(cell.name, seed, trace)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    config = cell.config
    shapes, ref = parts_of(config)
    api = sut = traffic = None
    try:
        api = rig.ApiServer(out_dir)
        nodes = shapes.Nodes(config["nodes"], seed)
        items = nodes.to_json()
        for i in range(0, len(items), 1000):
            chunk = items[i:i + 1000]
            api.post_list("nodes", json.dumps(
                {"kind": "List", "items": chunk}).encode(), len(chunk))
        pods = shapes.Pods(config["pods"], seed, config["nodes"])
        t_fill = time.monotonic()
        resident = prefill(api, ref, nodes, pods, int(config["resident_cap"]))
        prefill_s = time.monotonic() - t_fill
        log(f"apiserver up, {nodes.n} nodes and {len(resident)} resident "
            f"pods created")
        # Nodes (and the resident pods) FIRST: prewarm no-ops on an empty
        # cluster, and the daemon lists what runs there as any restart does.
        sut = (make_sut or rig.Daemon)(api.url, config, platform, out_dir)
        pods.grow(200_000)            # while the daemon prewarms
        ready_s = sut.wait_prewarmed(1100)
        account = sut.account()
        lists_s = getattr(sut, "lists_s", None)
        log(f"daemon ready in {ready_s:.1f} s on {account['platform']} "
            f"{account['kind']} x{account['count']}"
            + (f" (its first lists were in after {lists_s:.1f} s)"
               if lists_s is not None else ""))
        if account["platform"] != platform or account["count"] < cell.chips:
            raise rig.RunFailure(
                f"the daemon runs on {account['platform']!r} x"
                f"{account['count']}, the cell needs {platform!r} x"
                f"{cell.chips}")

        # -- ramp ------------------------------------------------------------
        # the launch sizes the daemon says it compiled for: what the ramp
        # has to drive once with live pods (nothing here names a size)
        buckets = [int(k) for k in sut.vars().get("prewarmCacheStats", {})
                   if str(k).isdigit()]
        traffic = cell.kind.Generator(
            api.port, pods, config, cell.traffic, seed, seconds,
            from_rv=api.resource_version(), resident=resident,
            launch_buckets=buckets)
        traffic.start()
        deadline = time.monotonic() + RAMP_TIMEOUT_S
        steady_since = None
        ramp_not_steady = False
        compiles, next_poll = None, 0.0
        while True:
            sut.child.require_alive()
            now = time.monotonic()
            if traffic.book.errors:
                raise rig.RunFailure(f"ramp: {traffic.book.errors[:3]}")
            if now >= next_poll:
                # a compile after prewarm (first live shapes) belongs to
                # the ramp: steady state starts over when one is counted
                seen = sut.vars()["postPrewarmCompiles"]
                if seen != compiles:
                    compiles, steady_since = seen, None
                    deadline = now + RAMP_TIMEOUT_S    # a cold cache's ramp
                next_poll = now + 0.5
            if traffic.steady():
                steady_since = steady_since or now
                if now - steady_since >= SETTLE_S:
                    break
            else:
                steady_since = None
            if now > deadline:
                # The window opens all the same and the run is judged: a
                # system that leaves pods unbound never settles, and that
                # is an answer (`correct` false), not a failed run.
                ramp_not_steady = True
                log(f"no steady state in {RAMP_TIMEOUT_S:.0f} s: "
                    f"{traffic.book.n_bound} bound, "
                    f"{traffic.book.n_resident} resident, "
                    f"{traffic.book.n_pending} pending")
                break
            time.sleep(0.05)

        # -- window ----------------------------------------------------------
        gc.collect()
        gc.freeze()
        gc.disable()
        if trace:
            sut.ask("gc-watch", "gc-watch.ok")
        before = {"daemon": sut.metrics(), "apiserver": api.metrics()}
        cpu_open = time.process_time()
        t_open = time.monotonic()
        t_close = t_open + seconds
        setup_s = t_open - T0
        traffic.open_window(t_open, t_close)
        log(f"window open after {setup_s:.1f} s of set-up "
            f"(ramp {t_open - traffic.t_start:.1f} s)")
        time.sleep(max(t_close - time.monotonic(), 0))
        t_closed = time.monotonic()
        cpu_close = time.process_time()
        after = {"daemon": sut.metrics(), "apiserver": api.metrics()}
        gc_pauses = json.loads(sut.ask("gc-read", "gc.json")) if trace else {}
        pending_at_close = max(traffic.book.n_pending, 0)   # read racily
        gc.enable()
        log(f"window closed: {traffic.binds_in(t_open, t_close)} binds seen")

        # The traced span follows the window, the traffic unchanged: the
        # profiler's cost and its slow stop stay out of every window number.
        trace_span = span_counters = None
        trace_dir = os.path.join(out_dir, "trace")
        if trace:
            sut.ask(f"trace-start {trace_dir}", "trace-start.ok")
            span_open = sut.metrics()
            t_a = time.monotonic()
            time.sleep(TRACE_SPAN_S)
            t_b = time.monotonic()
            span_counters = (span_open, sut.metrics())
            # The profiler's stop takes 20-40 s of the daemon's CPU: the
            # traffic ends with the span, not with the stop.
            traffic.stop_creating()
            sut.ask("trace-stop", "trace-stop.ok", timeout_s=240)
            trace_span = (t_a, t_b)
            log(f"traced {t_b - t_a:.2f} s after the window; stop took "
                f"{time.monotonic() - t_b:.1f} s")

        # -- close -----------------------------------------------------------
        traffic.stop_creating()
        deadline = time.monotonic() + DRAIN_TIMEOUT_S
        while not traffic.drained() and time.monotonic() < deadline:
            sut.child.require_alive()
            time.sleep(0.05)
        drained = traffic.drained()
        deadline = time.monotonic() + 15.0
        while not traffic.settled() and time.monotonic() < deadline:
            time.sleep(0.05)
        time.sleep(0.2)
        traffic.stop()
        log(f"closed: drained={drained}, {traffic.book.n_created} created, "
            f"{traffic.book.n_bound} bound, "
            f"{traffic.book.n_retire_acked} retired")
        final_list, _rv = api.list_pods()
        account = sut.account()
        device = json.loads(sut.ask("stats", "stats.json"))
        rc = sut.stop()
        sut = None
        if rc != 0:
            raise rig.RunFailure(f"the daemon did not exit 0 on SIGTERM "
                                 f"(code {rc})")
        api.stop()
        api = None
    except BaseException:
        for proc in (traffic, sut, api):
            if proc is not None:
                try:
                    proc.stop()
                except Exception:  # noqa: BLE001 — already failing
                    pass
        raise

    # -- judge (the program is gone; the reference runs alone) ---------------
    t_j = time.monotonic()
    correct, numbers, info = judging.judge(
        ref, nodes, pods, traffic.book, traffic.n_offered(), final_list,
        (t_open, t_close), seed, config, account, platform)
    numbers["ramp_not_steady"] = [int(ramp_not_steady), 0]
    correct = correct and not ramp_not_steady
    attempted, failed = traffic.attempted_failed()
    log(f"judged in {time.monotonic() - t_j:.1f} s: correct={correct} "
        f"{info}")

    binds = traffic.binds_in(t_open, t_close)
    runner = dict(traffic.report())
    runner.update({
        "pods_bound_per_s": binds / seconds,
        "setup_s": setup_s,
        "client_busy_pct": _percent(cpu_close - cpu_open, t_closed - t_open),
        "daemon_ready_s": ready_s,
        "daemon_lists_s": lists_s,
        "prefill_s": prefill_s,
        "ramp_s": t_open - traffic.t_start,
        "pending_at_close": float(pending_at_close),
    })
    if "gc_pause_s" in gc_pauses:     # the daemon's collector, window only
        runner["gc_pause_pct"] = _percent(gc_pauses["gc_pause_s"], seconds)
        runner["gc_pause_max_ms"] = gc_pauses["gc_pause_max_s"] * 1e3

    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": {}, "device": {
                  "platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": device["memory_peak_bytes"]}}
    if not trace:
        for m in cell.end_to_end():
            if runner.get(m["name"]) is None:
                raise rig.RunFailure(f"the run has no {m['name']}")
            result["metrics"][m["name"]] = {"value": runner[m["name"]],
                                            "unit": m["unit"]}
    else:
        reduced = None
        if trace_span is not None:
            reduced = reduce_trace_child(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)   # little on disk
        if not reduced or reduced["busy_s"] <= 0:
            raise rig.RunFailure("the traced span holds no device operation")
        ctx = {"daemon": (before["daemon"], after["daemon"]),
               "apiserver": (before["apiserver"], after["apiserver"]),
               "runner": runner, "pods_bound": binds, "trace": reduced,
               # the traced launches' own counts, not the window's
               "trace_pods": pods_scheduled(*span_counters),
               "pods_per_launch": pods_per_launch(*span_counters),
               "config": config, "device_kind": device["kind"]}
        for m, spec in cell.per_layer():
            reader = load_module("readers", spec["arithmetic"])
            value = reader.read(spec["args"], ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
            else:
                log(f"{m['name']}: nothing to read, left out of the line")
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        with open(os.path.join(out_dir, "trace_lines.json"), "w") as f:
            json.dump(reduced["lines"], f)
    # What else the run saw goes to a file of the run and to stderr; the
    # line holds the contract's keys and, last, the numbers compared.
    info["seed"] = seed
    info["seen"] = {k: v for k, v in runner.items()
                    if isinstance(v, (int, float))}
    info["client_cpu_s"] = {k: round(v, 3)
                            for k, v in traffic.book.cpu_s.items()}
    for key, family in (("relists", "reflector_relists_total"),
                        ("bind_conflicts", "scheduler_bind_conflicts_total")):
        info[key] = rig.family_sum(after["daemon"], family)
    with open(os.path.join(out_dir, "info.json"), "w") as f:
        json.dump(info, f)
    log(f"info {json.dumps(info)}")
    save_record(out_dir, traffic, final_list, (t_open, t_close), account)
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, (v, lim) in numbers.items()}
    if traffic.book.errors:
        print("client errors: " + "; ".join(traffic.book.errors[:5]),
              file=sys.stderr)
    for name, (v, lim) in numbers.items():     # the last lines of stderr
        print(f"compared {name}: {v} (limit {lim})"
              + ("" if v <= lim else "   <-- over its limit"),
              file=sys.stderr)
    return result


def prefill(api, ref, nodes, pods, count: int) -> list:
    """The cluster as the window finds it: pods ``0..count-1`` already
    bound where the configuration's plain reference scheduler ``ref``
    puts them, created with their ``nodeName`` before the daemon starts.
    The window's occupancy is then level from its first second, and the
    pods the daemon has to place in a run are the traffic's alone.
    Returns each one's node."""
    pods.grow(count)
    state = ref.State(nodes, pods)
    placed = []
    for start in range(0, count, 1000):
        stop = min(start + 1000, count)
        items = []
        for i in range(start, stop):
            best = ref.best_nodes(state, i)
            if not len(best):
                raise rig.RunFailure(f"resident pod {i} fits nowhere")
            # spread over the tied best nodes, as upstream's round robin does
            node = int(best[i % len(best)])
            state.add(i, node)
            placed.append(node)
            items.append(pods.json_bytes(i)[:-2]
                         + b',"nodeName":"node-%d"}}' % node)
        api.post_list("pods", b'{"kind":"List","items":['
                      + b",".join(items) + b"]}", stop - start)
    return placed


def _grew(before: dict, after: dict, family: str, labels: dict):
    a = rig.family_sum(after, family, labels)
    return None if a is None else a - (rig.family_sum(
        before, family, labels) or 0.0)


def pods_scheduled(before: dict, after: dict) -> float | None:
    """Pods the daemon placed between two reads of its counters."""
    return _grew(before, after, "scheduler_pod_scheduling_attempts_total",
                 {"result": "scheduled"})


def pods_per_launch(before: dict, after: dict) -> float | None:
    """Pods the daemon placed per device launch (one ``solve`` stage)
    between two reads of its counters."""
    pods = pods_scheduled(before, after)
    launches = _grew(before, after,
                     "scheduler_batch_stage_latency_microseconds_count",
                     {"stage": "solve"})
    return pods / launches if pods and launches else None


def save_record(out_dir: str, traffic, final_list: dict, window: tuple,
                account: dict) -> None:
    """The observer's record of the run, as the judge read it (about a
    megabyte): ``tests/rejudge.py`` reads it again, so a limit is set
    from the runs that were made and not from new ones."""
    import numpy as np
    ev = traffic.book.events
    np.savez_compressed(
        os.path.join(out_dir, "record.npz"),
        kind=np.array([e[0] for e in ev], np.int8),
        pod=np.array([e[1] for e in ev], np.int32),
        node=np.array([e[2] for e in ev], np.int32),
        t=np.array([e[3] for e in ev], np.float64),
        listed=np.array(sorted(final_list.items()), np.int64).reshape(-1, 2),
        window=np.array(window), n_created=traffic.book.n_created,
        n_offered=traffic.n_offered(), n_errors=len(traffic.book.errors),
        account=json.dumps(account))


def reduce_trace_child(trace_dir: str) -> dict | None:
    """The reduction needs ``jax.profiler.ProfileData``; this process
    never imports JAX, and the chip is free again (the daemon is gone)
    but is not touched: the child is pinned to the CPU."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "reduce_trace.py"), trace_dir],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        log(f"trace reduction failed: {proc.stderr[-500:]}")
        return None
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _on_signal(signum, _frame):
    raise rig.RunFailure(f"signal {signum}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = p.parse_args(argv)
    signal.signal(signal.SIGTERM, _on_signal)
    try:
        if not os.path.exists(os.path.join(
                rig.REPO, "kubernetes_tpu", "scheduler", "__main__.py")):
            raise rig.RunFailure("the program is not here: no "
                                 "kubernetes_tpu/scheduler/__main__.py")
        bench = load_json(os.path.join(rig.REPO, "BENCHMARK.json"))
        cell = Cell(bench, opts.workload)
        result = run_cell(cell, opts.seed, opts.seconds, bool(opts.trace))
    except (rig.RunFailure, OSError, KeyError, ValueError) as err:
        log(f"FAILED: {type(err).__name__}: {err}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
