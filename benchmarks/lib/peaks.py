"""Published peaks of the chips the benchmark may run on: one file per
device kind under ``peaks/``, named by the kind with every character that
a file name may not hold turned into ``_``."""

from __future__ import annotations

import json
import pathlib
import re

_DIR = pathlib.Path(__file__).resolve().parents[1] / "peaks"


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind with no file is an
    error, never a default."""
    path = _DIR / (re.sub(r"[^A-Za-z0-9._-]", "_", device_kind) + ".json")
    if not path.exists():
        known = sorted(json.loads(p.read_text())["device_kind"] for p in _DIR.glob("*.json"))
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; benchmarks/peaks/ lists {known}"
        )
    table = json.loads(path.read_text())
    if table["device_kind"] != device_kind:
        raise KeyError(f"{path.name} is for {table['device_kind']!r}, not {device_kind!r}")
    return table
