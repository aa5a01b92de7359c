"""Shared bench plumbing: the one-line JSON record every bench prints."""
from __future__ import annotations

import json
from typing import Optional

__all__ = ["emit_record"]


def emit_record(record: dict, out: Optional[str] = None) -> str:
    """Print the one-line JSON record; with ``out``, also write the
    committed pretty-printed BENCH_*.json form. Returns the line."""
    line = json.dumps(record)
    print(line)
    if out:
        with open(out, "w") as f:
            f.write(json.dumps(record, indent=1, sort_keys=True)
                    + "\n")
    return line
