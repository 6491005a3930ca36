"""The last line: one JSON object on standard output, the numbers compared
beside their limits as the last lines on standard error."""

from __future__ import annotations

import json
import sys


def emit(correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, compared: dict, breakdown: dict | None = None,
         extra: dict | None = None) -> None:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["compared"] = compared  # comes last
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
