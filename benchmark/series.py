"""The last value of a series in the trainer's ``metrics.jsonl``."""

from __future__ import annotations

import json
import os


def last(workdir: str, name: str) -> float | None:
    path = os.path.join(workdir, "metrics.jsonl")
    if not os.path.exists(path):
        return None
    value = None
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row.get("name") == name:
                value = float(row["value"])
    return value
