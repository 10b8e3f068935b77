"""bench_check — the perf-regression sentinel over a bench trajectory.

Input is a directory of archived bench records, one JSON file per round
(``BENCH_r<NN>.json``: ``{"n": round, ..., "parsed": {<the bench.py JSON
line>}}``). The tree keeps none of its own at present — the link-era
rounds were deleted in PR 21 and the per-cell ledger replaces this tool
when the benchmark PR lands (ROADMAP Design 6). It compares the CURRENT line
key-by-key against the best prior round, per metric, with per-class
tolerance bands::

    throughput (``*per_s*``, ``*_mb_s``, ``*_tf_s``)  current >= 0.9x best prior (max)
    tail latency (``*p99*``)                          current <= 1.25x best prior (min)
    byte ratios (``*bytes_ratio*``)                   exact == last prior

The round-18 token-serving keys ride the same bands —
``serve_generate_tokens_per_s`` is throughput,
``serve_generate_ttft_p99_ms``/``serve_generate_itl_p99_ms`` are tail
latency — plus :data:`LATENCY_GATED_P50` names median-latency keys
(e.g. ``serve_generate_ttft_p50_ms``) that gate under the p99 band
too: a median is far less weather-prone than a tail, so a 1.25x drift
there is a real regression, not a loaded box.

and exits **2 with a named-regressions report** when any gated metric
falls outside its band (``tools/trace.py``'s typed exit-2 discipline).
Metrics present only in the current line are reported as *new* (a
trajectory grows keys every round); metrics in :data:`VOLATILE` are
tracked and reported but never gated — they are host-I/O-bound probes
whose historical rounds swung more than 2x on identical code, so a
band tight enough to catch a real regression would page on weather.
The gated metrics are the seam-counted / latency-bound ones the
tier-1 perf gates also pin.

CLI::

    python tools/bench_check.py [--repo DIR] [--current FILE.json]
        [--throughput-band 0.9] [--p99-band 1.25]

Default: the newest round under ``--repo`` (the repo root) is the
current line, checked against all prior rounds; ``--current`` checks an
external line (either a bare bench.py JSON line or a full round record)
against the whole archived trajectory. ``bench.py --check`` runs the
same comparison in-process after archiving and stamps the verdict into
its JSON line (``bench_check_verdict``).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_THROUGHPUT_BAND = 0.9   # current >= band * best prior
DEFAULT_P99_BAND = 1.25         # current <= band * best prior

#: tracked-but-not-gated metrics: host-I/O-bound probes whose archived
#: rounds show >2x swings on identical code (shared-core CI boxes);
#: they stay in the report so a sustained cliff is still visible
VOLATILE = frozenset({
    "inference_images_per_s_per_chip",  # e2e incl. host decode/marshal
})


#: median-latency keys gated under the p99 band: medians of
#: high-sample-count token streams (TTFT over a whole burst) are stable
#: enough that the tail band is a meaningful floor for them too
LATENCY_GATED_P50 = frozenset({
    "serve_generate_ttft_p50_ms",
})


def classify(key: str) -> str | None:
    """Metric key → tolerance class (None = informational, ungated)."""
    if "bytes_ratio" in key:
        return "exact"
    if "p99" in key or key in LATENCY_GATED_P50:
        return "p99"
    if "per_s" in key or key.endswith("_mb_s") or key.endswith("_tf_s"):
        return "throughput"
    return None


def load_rounds(repo_dir: str) -> list[tuple[int, dict]]:
    """All archived rounds, ``[(n, parsed line), ...]`` sorted by round
    number. Unreadable or line-less records are skipped (a torn archive
    must not crash the sentinel)."""
    rounds: list[tuple[int, dict]] = []
    for path in glob.glob(os.path.join(repo_dir, "BENCH_r*.json")):
        try:
            with open(path, encoding="utf-8") as fh:
                rec = json.load(fh)
            parsed = rec.get("parsed")
            if isinstance(parsed, dict):
                rounds.append((int(rec.get("n", 0)), parsed))
        except (OSError, ValueError, TypeError):
            continue
    rounds.sort(key=lambda r: r[0])
    return rounds


def check_line(current: dict, priors: list[tuple[int, dict]],
               throughput_band: float = DEFAULT_THROUGHPUT_BAND,
               p99_band: float = DEFAULT_P99_BAND) -> dict:
    """Compare one bench line against the prior rounds. Returns the
    report: ``verdict`` (``"ok"`` / ``"regressed"`` / ``"no-priors"``),
    the named ``regressions`` (key, class, current, best prior + its
    round, the band), everything ``checked``, ``volatile`` tracked
    values, and ``new`` keys with no prior."""
    report: dict = {"verdict": "ok", "regressions": [], "checked": [],
                    "volatile": [], "new": [],
                    "rounds_compared": [n for n, _p in priors]}
    # within-line A/B: absolute model-load walls are box weather (a
    # cross-round band would flake on shared-core CI), but cold and warm
    # come from the same line on the same box minutes apart — a warm
    # compile-cache load costing MORE than the cold load that populated
    # the cache is a real regression regardless of the box
    cold = current.get("serve_load_wall_cold_s")
    warm = current.get("serve_load_wall_warm_s")
    if isinstance(cold, (int, float)) and not isinstance(cold, bool) \
            and isinstance(warm, (int, float)) \
            and not isinstance(warm, bool):
        row = {"key": "serve_load_wall_warm_s", "class": "within-line",
               "current": warm, "best": cold, "best_round": None,
               "ratio": round(warm / cold, 4) if cold else None,
               "band": "<= serve_load_wall_cold_s (same line)"}
        report["checked"].append(row)
        if warm > cold:
            report["regressions"].append(row)
    # same within-line discipline for the lifecycle deployer's
    # checkpoint→serving wall: the warm rollout rides the compile cache
    # the cold rollout populated, minutes apart on the same box
    d_cold = current.get("deploy_wall_cold_s")
    d_warm = current.get("deploy_wall_warm_s")
    if isinstance(d_cold, (int, float)) and not isinstance(d_cold, bool) \
            and isinstance(d_warm, (int, float)) \
            and not isinstance(d_warm, bool):
        row = {"key": "deploy_wall_warm_s", "class": "within-line",
               "current": d_warm, "best": d_cold, "best_round": None,
               "ratio": round(d_warm / d_cold, 4) if d_cold else None,
               "band": "<= deploy_wall_cold_s (same line)"}
        report["checked"].append(row)
        if d_warm > d_cold:
            report["regressions"].append(row)
    if not priors:
        report["verdict"] = ("regressed" if report["regressions"]
                             else "no-priors")
        return report
    for key in sorted(current):
        cls = classify(key)
        v = current.get(key)
        if cls is None or not isinstance(v, (int, float)) \
                or isinstance(v, bool):
            continue
        prior_vals = [(n, p[key]) for n, p in priors
                      if isinstance(p.get(key), (int, float))
                      and not isinstance(p.get(key), bool)]
        if not prior_vals:
            report["new"].append(key)
            continue
        if cls == "throughput":
            best_n, best = max(prior_vals, key=lambda nv: nv[1])
            ok = v >= throughput_band * best
            band = f">= {throughput_band:g}x best"
        elif cls == "p99":
            best_n, best = min(prior_vals, key=lambda nv: nv[1])
            ok = v <= p99_band * best
            band = f"<= {p99_band:g}x best"
        else:  # exact
            best_n, best = prior_vals[-1]
            ok = v == best
            band = "== last"
        row = {"key": key, "class": cls, "current": v, "best": best,
               "best_round": best_n,
               "ratio": round(v / best, 4) if best else None,
               "band": band}
        if key in VOLATILE:
            report["volatile"].append({**row, "gated": False})
            continue
        report["checked"].append(row)
        if not ok:
            report["regressions"].append(row)
    if report["regressions"]:
        report["verdict"] = "regressed"
    return report


def format_report(report: dict) -> str:
    """The human lines the CLI prints under the JSON verdict."""
    lines = [f"bench_check: {report['verdict']} — "
             f"{len(report['checked'])} gated metric(s) vs rounds "
             f"{report['rounds_compared']}"]
    for r in report["regressions"]:
        lines.append(
            f"  REGRESSION {r['key']} [{r['class']}]: "
            f"{r['current']} vs best {r['best']} (r{r['best_round']}) "
            f"— {r['ratio']}x, band {r['band']}")
    for r in report["volatile"]:
        lines.append(
            f"  volatile (not gated) {r['key']}: {r['current']} vs "
            f"best {r['best']} ({r['ratio']}x)")
    if report["new"]:
        lines.append(f"  new (no prior): {', '.join(report['new'])}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="bench_check", description=__doc__,
                                 formatter_class=argparse.
                                 RawDescriptionHelpFormatter)
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding the BENCH_r*.json trajectory")
    ap.add_argument("--current", default=None,
                    help="JSON file to check against the WHOLE "
                         "trajectory (a bench.py line, or a round "
                         "record with a 'parsed' key); default: the "
                         "newest archived round vs its priors")
    ap.add_argument("--throughput-band", type=float,
                    default=DEFAULT_THROUGHPUT_BAND)
    ap.add_argument("--p99-band", type=float, default=DEFAULT_P99_BAND)
    args = ap.parse_args(argv if argv is not None else sys.argv[1:])

    rounds = load_rounds(args.repo)
    if args.current:
        try:
            with open(args.current, encoding="utf-8") as fh:
                current = json.load(fh)
        except (OSError, ValueError) as e:
            print(f"bench_check: cannot read --current "
                  f"{args.current!r}: {e}", file=sys.stderr)
            return 2
        if isinstance(current, dict) and isinstance(
                current.get("parsed"), dict):
            current = current["parsed"]
        if not isinstance(current, dict):
            print(f"bench_check: {args.current!r} is not a bench line",
                  file=sys.stderr)
            return 2
        priors = rounds
    else:
        if not rounds:
            print(f"bench_check: no BENCH_r*.json rounds under "
                  f"{args.repo!r}", file=sys.stderr)
            return 2
        current = rounds[-1][1]
        priors = rounds[:-1]

    report = check_line(current, priors,
                        throughput_band=args.throughput_band,
                        p99_band=args.p99_band)
    print(json.dumps({"bench_check": report["verdict"],
                      "regressions": [r["key"] for r in
                                      report["regressions"]],
                      "checked": len(report["checked"]),
                      "volatile": len(report["volatile"]),
                      "new": len(report["new"])}))
    print(format_report(report))
    return 2 if report["verdict"] == "regressed" else 0


if __name__ == "__main__":
    sys.exit(main())
