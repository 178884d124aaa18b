"""The result line, and the comparison lines that stand beside it."""

from __future__ import annotations

import json
import sys


def check_lines(checks: dict) -> list:
    """One plain line per number compared: name, number, limit, verdict."""
    return [
        f"check {name}: value {c['value']!r} limit {c['limit']!r} "
        f"{'ok' if c['ok'] else 'FAIL'}"
        for name, c in checks.items()
    ]


def emit(result: dict, stdout=None, stderr=None) -> None:
    """Print the comparison as the last lines of standard error and the
    result as the last line of standard output, ``checks`` last in it."""
    stdout = stdout or sys.stdout
    stderr = stderr or sys.stderr
    checks = result.pop("checks")
    for line in check_lines(checks):
        print(line, file=stderr)
    stderr.flush()
    result["checks"] = {
        k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()
    }
    print(json.dumps(result), file=stdout)
    stdout.flush()
