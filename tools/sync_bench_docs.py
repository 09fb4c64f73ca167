#!/usr/bin/env python
"""Regenerate the performance blocks in README.md / ARCHITECTURE.md from the
newest committed BENCH_r{N}.json.

Three rounds in a row shipped stale headline numbers somewhere in the docs
(VERDICT r3 weak #7); the fix is the process, not another hand edit: the
numbers between the ``<!-- bench:begin -->`` / ``<!-- bench:end -->``
markers are machine-rendered from the artifact, and
``tests/test_docs_bench_sync.py`` fails the suite whenever the rendered
form and the committed docs disagree.

Usage: ``python tools/sync_bench_docs.py`` (rewrites both files in place).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BEGIN = "<!-- bench:begin -->"
END = "<!-- bench:end -->"


def _committed_bench_names() -> set[str] | None:
    """BENCH artifacts tracked by git, or None when git is unavailable
    (zero tracked artifacts returns an EMPTY set: the ratchet then
    refuses uncommitted ones instead of silently falling back to them).

    The docs ratchet compares against the newest COMMITTED artifact: a
    BENCH_r{N}.json dropped into the worktree after the docs were last
    synced (the bench driver writes one post-commit every round) must not
    turn the suite red — the docs were correct at the snapshot they were
    committed with ("green at snapshot")."""
    try:
        # ls-tree against HEAD, not ls-files: the index sees staged-but-
        # uncommitted artifacts, which are exactly what the ratchet must
        # ignore ("green at snapshot" = green against the last commit).
        out = subprocess.run(
            ["git", "-C", REPO, "ls-tree", "-r", "--name-only", "HEAD"],
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return None
    if out.returncode != 0:
        return None
    return {n for n in out.stdout.splitlines()
            if re.fullmatch(r"BENCH_r\d+\.json", n)}


def latest_bench() -> tuple[str, dict]:
    """(tag, parsed) for the highest-numbered committed BENCH_r*.json
    (falls back to all present artifacts outside a git checkout)."""
    committed = _committed_bench_names()
    best_n, best = -1, None
    for name in os.listdir(REPO):
        m = re.fullmatch(r"BENCH_r(\d+)\.json", name)
        if not m:
            continue
        if committed is not None and name not in committed:
            continue
        with open(os.path.join(REPO, name)) as f:
            data = json.load(f)
        parsed = data.get("parsed")
        if parsed and int(m.group(1)) > best_n:
            best_n, best = int(m.group(1)), (name, parsed)
    if best is None:
        raise SystemExit("no BENCH_r*.json with a parsed payload found")
    return best


def _shape(parsed: dict) -> tuple[int, int]:
    m = re.search(r"([\d,]+) pods onto ([\d,]+) nodes", parsed["metric"])
    if not m:
        return 30000, 5000
    return (int(m.group(1).replace(",", "")),
            int(m.group(2).replace(",", "")))


def _cold_warm(parsed: dict) -> tuple[float | None, float | None]:
    """(cold_s, warm_s) for the cold/warm-start columns, read ONLY from
    the dedicated ``cold_vs_warm`` phase — artifacts predating it
    measured their warm trace without the persistent compilation cache,
    and rendering those numbers under a 'persistent XLA cache' caption
    would attribute a result the artifact never measured."""
    cw = parsed.get("cold_vs_warm") or {}
    return cw.get("cold_compile_s"), cw.get("warm_start_compile_s")


def _hw(parsed: dict) -> str:
    """Human caption for the artifact's measured backend (absent =
    BENCH_r05's TPU rig, which predates the field)."""
    backend = parsed.get("backend") or "tpu"
    if backend == "tpu":
        return "one TPU v5e chip"
    return f"the JAX {backend} backend (no accelerator attached)"


def render_readme(tag: str, parsed: dict) -> str:
    pods, nodes = _shape(parsed)
    pps = parsed["value"]
    secs = pods / pps
    lines = [
        f"Measured on {_hw(parsed)} ({tag.removesuffix('.json')}): "
        f"**{pods:,} pods onto {nodes:,} nodes in {secs:.2f} s end-to-end "
        f"({pps:,.0f} pods/s)** through the full daemon path — "
        f"~{parsed['vs_baseline']:,.0f}× the reference's 8 pods/s "
        f"cluster-saturation floor"]
    wire = parsed.get("wire")
    if wire:
        lines[-1] += (
            f"; the same shape across a REAL process boundary (apiserver "
            f"in its own process, daemon joined by HTTP list/watch/bind "
            f"at QPS 5000) runs at **{wire['pods_per_second']:,.0f} "
            f"pods/s**")
    joint = parsed.get("joint")
    if joint:
        lines[-1] += (
            f".  The LP-joint solve places "
            f"{(joint['joint_vs_greedy'] - 1) * 100:+.0f}% vs greedy on an "
            f"overcommitted fleet")
    lines[-1] += "."
    cold, warm = _cold_warm(parsed)
    if cold is not None and warm is not None:
        lines.append(
            f"Start-up compile: {cold:.1f} s cold (once per machine), "
            f"{warm:.1f} s warm-start against the persistent XLA "
            f"compilation cache.")
    fleet = parsed.get("fleet")
    if fleet:
        lines.append(
            f"At kubemark scale ({fleet['nodes']} hollow kubelets, "
            f"{fleet['replicas']:,} replicas driven to Running), the "
            f"replication manager's full resync costs "
            f"{fleet['rc_full_resync_ms']:.0f} ms and an idle dirty pass "
            f"{fleet['rc_idle_dirty_pass_ms']:.2f} ms.")
    return "\n".join(lines)


def _stage_cell(stages: dict) -> str:
    """'solve 0.42 s · bind 0.31 s · ...' — stages sorted by time desc."""
    items = sorted(stages.items(),
                   key=lambda kv: -kv[1].get("seconds", 0.0))
    return " · ".join(f"{name} {d.get('seconds', 0.0):.2f} s"
                      for name, d in items)


def _profile_cell(prof: dict) -> str:
    """'solve_host 62% · serialize 21% · …; decode 38 µs/ev, …' — the
    kt-prof component split plus per-event wire costs."""
    frac = prof.get("cpu_fraction") or {}
    top = sorted(frac.items(), key=lambda kv: -kv[1])[:4]
    parts = []
    if top:
        parts.append(" · ".join(f"{c} {v:.0%}" for c, v in top))
    wire = prof.get("wire") or {}
    per = [f"{name} {wire[name][key]:.0f} µs/ev"
           for name, key in (("decode", "us_per_event"),
                             ("handler", "us_per_event"),
                             ("serialize", "us_per_op"))
           if name in wire]
    if per:
        parts.append(", ".join(per))
    return "; ".join(parts)


def render_arch(tag: str, parsed: dict) -> str:
    pods, nodes = _shape(parsed)
    pps = parsed["value"]
    secs = pods / pps
    tagc = tag.removesuffix(".json")
    rows = [
        "| Shape | e2e (queue→solve→assume→bind) | vs 8 pods/s floor |",
        "|---|---|---|",
        f"| {pods // 1000}k pods / {nodes // 1000}k nodes, in-process "
        f"binder | {secs:.3f} s ≈ {pps:,.0f} pods/s | "
        f"~{parsed['vs_baseline']:,.0f}× |"]
    wire = parsed.get("wire")
    if wire:
        apiserver = wire.get("apiserver", "python")
        rows.append(
            f"| same, over HTTP (apiserver [{apiserver}] in its own "
            f"process, live pod arrivals, binds at QPS 5000) | "
            f"{wire['elapsed_s']:.1f} s ≈ {wire['pods_per_second']:,.0f} "
            f"pods/s | ~{wire['pods_per_second'] / 8:,.0f}× |")
    # Per-stage breakdown rows (artifacts produced before the stage
    # histogram existed simply omit them).
    if parsed.get("stages"):
        rows.append(f"| ↳ density stage breakdown | "
                    f"{_stage_cell(parsed['stages'])} | — |")
    if wire and wire.get("stages"):
        rows.append(f"| ↳ wire stage breakdown (daemon side) | "
                    f"{_stage_cell(wire['stages'])} | — |")
    # kt-prof CPU attribution rows (artifacts predating the profile
    # section, or stamped with KT_PROF=0, omit them).
    prof = parsed.get("profile")
    if prof and prof.get("enabled"):
        rows.append(f"| ↳ density CPU attribution (kt-prof) | "
                    f"{_profile_cell(prof)} | — |")
    wprof = (wire or {}).get("profile")
    if wprof and wprof.get("enabled"):
        rows.append(f"| ↳ wire CPU attribution (daemon side) | "
                    f"{_profile_cell(wprof)} | — |")
    cold, warm = _cold_warm(parsed)
    if cold is not None and warm is not None:
        rows.append(
            f"| start-up compile (cold / warm via persistent XLA cache) "
            f"| {cold:.1f} s cold → {warm:.1f} s warm | — |")
    lines = [f"Numbers from `{tagc}.json` (best of "
             f"{len(parsed.get('runs', [1]))}; median "
             f"{parsed.get('median', parsed['value']):,.0f} pods/s):", ""]
    lines.extend(rows)
    fleet = parsed.get("fleet")
    if fleet:
        lines.append(
            f"| kubemark fleet: {fleet['nodes']} hollow kubelets, "
            f"{fleet['replicas']:,} replicas | settle "
            f"{fleet['settle_s']:.0f} s; RC full resync "
            f"{fleet['rc_full_resync_ms']:.0f} ms, idle pass "
            f"{fleet['rc_idle_dirty_pass_ms']:.2f} ms; heartbeats "
            f"{fleet['heartbeat_writes_per_s']:.0f} writes/s | — |")
    return "\n".join(lines)


def splice(text: str, block: str) -> str:
    pattern = re.compile(re.escape(BEGIN) + r".*?" + re.escape(END),
                         re.DOTALL)
    if not pattern.search(text):
        raise SystemExit("bench markers not found")
    return pattern.sub(BEGIN + "\n" + block + "\n" + END, text)


def main() -> int:
    tag, parsed = latest_bench()
    changed = False
    for path, renderer in (("README.md", render_readme),
                           ("ARCHITECTURE.md", render_arch)):
        full = os.path.join(REPO, path)
        with open(full) as f:
            text = f.read()
        new = splice(text, renderer(tag, parsed))
        if new != text:
            with open(full, "w") as f:
                f.write(new)
            changed = True
            print(f"updated {path} from {tag}")
    if not changed:
        print(f"docs already in sync with {tag}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
