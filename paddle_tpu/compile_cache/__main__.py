"""`python -m paddle_tpu.compile_cache report` — cold-start timeline CLI.

Reads a ledger dump (`compile_cache.ledger.dump_json(path)`, written by
bench / dryrun / a serving process at shutdown) and prints the
engine-load -> first-token decomposition; `--json` emits the raw report
dict.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Sequence

from . import ledger, report


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.compile_cache",
        description="compile-ledger cold-start timeline report",
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser("report", help="cold-start timeline from a ledger dump")
    rp.add_argument("--input", "-i", default=None,
                    help="ledger dump path (default: the live in-process "
                         "ledger — useful only under `python -c` drivers)")
    rp.add_argument("--json", action="store_true", help="emit the raw dict")
    args = p.parse_args(argv)

    data = None
    if args.input:
        try:
            data = ledger.load_dump(args.input)
        except (OSError, ValueError) as e:
            print(f"compile_cache: unreadable dump {args.input}: {e}",
                  file=sys.stderr)
            return 2
    rep = report.cold_start_report(data)
    if args.json:
        print(json.dumps(rep, indent=1, sort_keys=True))
    else:
        print(report.format_report(rep))
    return 0 if rep.get("available") else 1


if __name__ == "__main__":
    sys.exit(main())
