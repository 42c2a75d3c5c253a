#!/usr/bin/env python
"""graftlint CLI — run the repo's static-analysis passes (no JAX backend).

Usage:
    python scripts/lint.py                  # human output, exit 1 on findings
    python scripts/lint.py --json out.json  # CI artifact (also - for stdout)
    python scripts/lint.py --rule drift     # one pass family
    python scripts/lint.py --list-rules
    python scripts/lint.py --raw            # include allowlisted findings

Exit codes: 0 clean, 1 findings, 2 internal error. The tier-1 runner
(scripts/tier1.sh) runs this BEFORE the tests: it finishes in
seconds because nothing here imports jax — `veomni_tpu.analysis` is
import-light by design, and this script asserts that property so a future
import can't silently turn the lint stage into a backend init.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--json", metavar="PATH",
                    help="write findings as JSON to PATH ('-' for stdout)")
    ap.add_argument("--rule", help="run only rules under this prefix "
                    "(pass family or full rule id)")
    ap.add_argument("--raw", action="store_true",
                    help="also show allowlist-suppressed findings")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--root", default=_REPO)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    from veomni_tpu.analysis import get_passes, run_lint

    if args.list_rules:
        for p in get_passes():
            print(f"{p.name:<18} {p.description}")
        return 0

    result = run_lint(args.root, rules=args.rule)
    dt = time.perf_counter() - t0

    # the whole point of the fast lint stage: no backend, ever
    assert "jax" not in sys.modules, (
        "graftlint imported jax — the lint stage must stay backend-free"
    )

    if args.json:
        doc = {
            "ok": result.ok,
            "elapsed_s": round(dt, 3),
            "suppressed": result.suppressed,
            "findings": [f.to_doc() for f in result.findings],
        }
        if args.raw:
            doc["raw_findings"] = [f.to_doc() for f in result.raw_findings]
        payload = json.dumps(doc, indent=2)
        if args.json == "-":
            print(payload)
        else:
            parent = os.path.dirname(args.json)
            if parent:
                os.makedirs(parent, exist_ok=True)
            with open(args.json, "w") as f:
                f.write(payload + "\n")

    shown = result.findings if not args.raw else result.raw_findings
    for f in shown:
        print(f.format())
    status = "clean" if result.ok else f"{len(result.findings)} finding(s)"
    print(
        f"graftlint: {status} ({result.suppressed} allowlisted, "
        f"{dt:.2f}s, no JAX)", file=sys.stderr,
    )
    return 0 if result.ok else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # pragma: no cover - CI wants a distinct code
        print(f"graftlint: internal error: {e}", file=sys.stderr)
        raise SystemExit(2)
