"""CI perf-regression gate: compare a fresh fig4_pipelines run against
the last committed ``BENCH_pipelines.json`` entry and fail on a
tuned-plan throughput regression.

    python -m benchmarks.check_regression \\
        --baseline /tmp/BENCH_baseline.json --fresh BENCH_pipelines.json \\
        [--threshold 0.25] [--metric t_pallas_tuned_s[,more...]]

Mechanics:
  * ``--baseline`` is the accumulator file **as committed** (CI copies
    it aside before the bench run, because fig4 appends to the same
    file); ``--fresh`` is the file after the new run.  The LAST run
    record in each is compared.
  * Pipelines are matched on ``(pipeline, n)``; pairs present on only
    one side are reported and skipped (new pipelines don't fail the
    gate, removed ones don't either — the reviewer sees both).
  * Throughput is 1/t on ``--metric`` (default: the tuned all-Pallas
    plan time, the number the autotuning work defends), **normalized by
    the same record's** ``--relative-to`` **field** (default: the per-op
    dispatch time).  The committed baseline was timed on whatever
    machine the developer used; the fresh run executes on a CI runner —
    absolute seconds don't compare across them, but tuned-plan time
    relative to the same machine's per-op baseline does (machine speed
    cancels in the ratio, a genuine kernel/plan regression doesn't).
    Pass ``--relative-to ''`` to gate on absolute seconds.  A pair
    fails when fresh (normalized) throughput drops more than
    ``--threshold`` (default 25%) below baseline:
    ``t_fresh > t_base / (1 - threshold)``.
  * ``--higher-is-better`` flips the gate into a quality FLOOR for
    metrics where bigger is better (``int8_sqnr_db``): a pair fails
    when ``fresh < base * (1 - threshold)``.  Quality metrics are
    machine-independent, so pair it with ``--relative-to ''``.
  * Both flags accept a comma-separated LIST, zipped positionally
    (``--relative-to`` may also be a single value, broadcast to every
    metric; empty entries mean absolute).  One invocation then gates
    several time fields of the same file — e.g. ``--metric
    t_plan_s,t_pallas_tuned_s --relative-to t_per_op_s`` gates both
    plan times against the same run's per-op dispatch time.  Every
    (pipeline, n, metric) triple is gated independently.

Waiver: a commit that knowingly trades this throughput away (e.g. a
correctness fix in a kernel) adds one line to its message::

    bench-waiver: <why the regression is accepted>

The gate scans ``$BENCH_COMMIT_MSG`` if set (CI passes the head commit
message through it; a fetch-depth-1 checkout may not have usable git
history), else ``git log -1 --format=%B``.  A present waiver downgrades
failures to warnings (exit 0) and prints the reason into the log.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

WAIVER_PREFIX = "bench-waiver:"


def last_run(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and isinstance(data.get("runs"), list):
        if not data["runs"]:
            raise SystemExit(f"{path}: empty runs list")
        return data["runs"][-1]
    if isinstance(data, dict) and "results" in data:
        return data                       # legacy single-run format
    raise SystemExit(f"{path}: not a BENCH accumulator file")


def index_results(run: dict, metric: str,
                  relative_to: str | None = None,
                  floor_mode: bool = False) -> dict[tuple, float]:
    out = {}
    for rec in run.get("results", []):
        t = rec.get(metric)
        if t is None or not isinstance(t, (int, float)):
            continue
        if not floor_mode and t <= 0:
            continue              # a time of 0 is unusable, skip
        if relative_to:
            ref = rec.get(relative_to)
            if not ref or ref <= 0:
                continue          # can't normalize: skip, don't misgate
            t = t / ref
        out[(rec.get("pipeline"), rec.get("n"))] = float(t)
    return out


def parse_metrics(metric: str, relative_to: str) -> list[tuple[str, str | None]]:
    """Zip the comma-separated ``--metric`` / ``--relative-to`` values
    into (metric, ref_or_None) pairs.  A single relative-to is broadcast
    across every metric; empty entries gate on absolute values."""
    metrics = [m.strip() for m in metric.split(",") if m.strip()]
    if not metrics:
        raise SystemExit("--metric: no metric names given")
    refs = [r.strip() for r in relative_to.split(",")] if relative_to else [""]
    if len(refs) == 1:
        refs = refs * len(metrics)
    if len(refs) != len(metrics):
        raise SystemExit(
            f"--relative-to: {len(refs)} entries for {len(metrics)} "
            "metrics (give one per metric, or one for all)")
    return [(m, r or None) for m, r in zip(metrics, refs)]


def _scan(msg: str | None) -> str | None:
    for line in (msg or "").splitlines():
        if line.strip().lower().startswith(WAIVER_PREFIX):
            return line.strip()[len(WAIVER_PREFIX):].strip() or "(no reason)"
    return None


def _git_msg(*rev: str) -> str:
    try:
        return subprocess.run(
            ["git", "log", "-1", "--format=%B", *rev],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout
    except (OSError, subprocess.SubprocessError):
        return ""


def find_waiver(commit_msg: str | None = None) -> str | None:
    """The waiver line, scanning every plausible source until one hits:
    the explicit argument, ``$BENCH_COMMIT_MSG`` (CI passes the push
    head-commit message — or the PR title — through it), ``git log -1``
    (the checked-out commit), and ``HEAD^2`` (on a pull_request run the
    checkout is a merge commit whose second parent is the PR head, where
    the contributor actually wrote the waiver line; the CI job fetches
    depth 2 so it resolves).  Sources without a waiver don't mask later
    ones — a PR-title env value must not suppress the commit-message
    waiver the gate's own failure text tells contributors to write."""
    for msg in (commit_msg, os.environ.get("BENCH_COMMIT_MSG"),
                _git_msg(), _git_msg("HEAD^2")):
        hit = _scan(msg)
        if hit is not None:
            return hit
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_pipelines.json (copied aside "
                         "before the fresh bench run)")
    ap.add_argument("--fresh", required=True,
                    help="BENCH_pipelines.json after the fresh run")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="max tolerated throughput drop (fraction)")
    ap.add_argument("--metric", default="t_pallas_tuned_s",
                    help="per-result time field(s) to gate on, "
                         "comma-separated")
    ap.add_argument("--relative-to", default="t_per_op_s",
                    help="same-record field(s) the metric is divided by "
                         "before comparing, so baseline and fresh runs "
                         "on different machines stay comparable "
                         "(machine speed cancels in the ratio); "
                         "comma-separated, zipped with --metric (one "
                         "value broadcasts); '' gates on absolute time")
    ap.add_argument("--commit-msg", default=None,
                    help="commit message to scan for the waiver line "
                         "(default: $BENCH_COMMIT_MSG, then git log -1)")
    ap.add_argument("--higher-is-better", action="store_true",
                    help="gate the metric as a FLOOR instead of a "
                         "latency ceiling: fail when the fresh value "
                         "drops more than --threshold below baseline "
                         "(for quality metrics like int8_sqnr_db; "
                         "values <= 0 are gated, not skipped). Applies "
                         "to every metric in this invocation — run the "
                         "gate twice to mix directions")
    args = ap.parse_args(argv)

    base_run = last_run(args.baseline)
    fresh_run = last_run(args.fresh)
    pairs = parse_metrics(args.metric, args.relative_to)
    print(f"[bench-gate] baseline run {base_run.get('git_rev')} "
          f"({base_run.get('timestamp')}), fresh run "
          f"{fresh_run.get('git_rev')} ({fresh_run.get('timestamp')}); "
          f"threshold {args.threshold:.0%}")

    failures, any_overlap = [], False
    for metric, rel in pairs:
        base = index_results(base_run, metric, rel, args.higher_is_better)
        fresh = index_results(fresh_run, metric, rel, args.higher_is_better)
        unit = f"x {rel}" if rel else "absolute"
        kind = "floor" if args.higher_is_better else "ceiling"
        print(f"[bench-gate] metric {metric} ({unit}, {kind})")
        for key in sorted(set(base) - set(fresh)):
            print(f"[bench-gate] note: {key} only in baseline (skipped)")
        for key in sorted(set(fresh) - set(base)):
            print(f"[bench-gate] note: {key} only in fresh run (skipped)")
        any_overlap = any_overlap or bool(set(base) & set(fresh))
        for key in sorted(set(base) & set(fresh)):
            t_base, t_fresh = base[key], fresh[key]
            if args.higher_is_better:
                # quality floor: fresh value itself is the goodness
                ratio = t_fresh / t_base
                bad = t_fresh < t_base * (1.0 - args.threshold)
                label = "of baseline"
            else:
                ratio = t_base / t_fresh  # fresh throughput / baseline
                bad = t_fresh > t_base / (1.0 - args.threshold)
                label = "throughput"
            status = "OK"
            if bad:
                status = "REGRESSION"
                failures.append((*key, metric))
            print(f"[bench-gate] {key[0]} n={key[1]} {metric}: "
                  f"{t_base:.4g} -> {t_fresh:.4g} "
                  f"({ratio:.2f}x {label})  {status}")

    if not any_overlap:
        print("[bench-gate] WARNING: no overlapping (pipeline, n) pairs — "
              "nothing gated")
    if not failures:
        print("[bench-gate] PASS")
        return 0
    waiver = find_waiver(args.commit_msg)
    if waiver is not None:
        print(f"[bench-gate] {len(failures)} regression(s) WAIVED: {waiver}")
        return 0
    print(f"[bench-gate] FAIL: {len(failures)} (pipeline, n, metric) "
          f"triple(s) lost more than "
          f"{args.threshold:.0%} throughput: {failures}\n"
          f"[bench-gate] to accept knowingly, add a commit-message line: "
          f"'{WAIVER_PREFIX} <reason>'")
    return 1


if __name__ == "__main__":
    sys.exit(main())
