#!/usr/bin/env python
"""Scenario lab runner (ISSUE 20 tentpole CLI).

Executes the declarative workload/fault scenarios in
``mqtt_tpu/scenarios.py`` — each one a seeded fleet + traffic mix +
fault script judged by a delivery oracle AND the SLO engine's
burn-rate objectives — and writes a JSON artifact (``--out``, default
``exp/artifacts/scenario_lab.json``) with the full per-scenario result
docs (oracle counts, SLO objective states, driver metrics, wall time,
seed) for CI upload: the lab's one record.

Usage:
    python exp/scenario_lab.py --smoke            # CI verify-job gate
    python exp/scenario_lab.py --all              # nightly full matrix
    python exp/scenario_lab.py tenant_rekey       # one scenario, ad hoc
    python exp/scenario_lab.py --all --seed 7     # reseeded
Exit code is non-zero when any selected scenario fails its oracle or
breaches an SLO objective.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from mqtt_tpu.scenarios import SCENARIOS, run_matrix, scenario_names  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "names",
        nargs="*",
        help=f"scenario names to run (known: {', '.join(SCENARIOS)})",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="run only the smoke-tier scenarios (CI verify job)",
    )
    ap.add_argument(
        "--all", action="store_true", help="run the full scenario matrix"
    )
    ap.add_argument(
        "--seed",
        type=int,
        default=None,
        help="override every spec's seed",
    )
    ap.add_argument(
        "--out",
        default=os.path.join(_REPO, "exp", "artifacts", "scenario_lab.json"),
        help="artifact path for the full result docs",
    )
    ap.add_argument("--list", action="store_true", help="list scenarios")
    args = ap.parse_args()

    if args.list:
        for name, spec in SCENARIOS.items():
            tier = "smoke" if spec.smoke else "full "
            print(f"{name:20s} [{tier}] seed={spec.seed}  {spec.title}")
        return 0

    if args.names:
        unknown = [n for n in args.names if n not in SCENARIOS]
        if unknown:
            ap.error(f"unknown scenario(s): {', '.join(unknown)}")
        names, selection = list(args.names), "custom"
    elif args.all:
        names, selection = scenario_names(), "full"
    elif args.smoke:
        names, selection = scenario_names(smoke_only=True), "smoke"
    else:
        ap.error("pick scenarios by name, or pass --smoke / --all")
        return 2  # unreachable; keeps type-checkers honest

    print(f"scenario-lab: running {len(names)} scenario(s): {', '.join(names)}")
    results = run_matrix(names, seed=args.seed)

    failed = [r["scenario"] for r in results if not r.get("passed")]
    for r in results:
        oracle = r.get("oracle") or {}
        mark = "PASS" if r.get("passed") else "FAIL"
        print(
            f"scenario-lab: [{mark}] {r['scenario']:18s} "
            f"delivered {oracle.get('delivered', 0)}/{oracle.get('expected', 0)} "
            f"gaps={oracle.get('gaps', 0)} dups={oracle.get('duplicates', 0)} "
            f"wall={r.get('wall_s', 0):.2f}s"
        )
        for msg in r.get("failures") or []:
            print(f"scenario-lab:        - {msg}")

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    artifact = {
        "selection": selection,
        "seed_override": args.seed,
        "passed": not failed,
        "results": results,
    }
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(artifact, f, indent=2, default=str)
    print(f"scenario-lab: artifact written to {args.out}")

    if failed:
        print(f"scenario-lab: FAILED: {', '.join(failed)}")
        return 1
    print(f"scenario-lab: all {len(results)} scenario(s) passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
