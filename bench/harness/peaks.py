"""The chips' published peaks, keyed by ``device_kind`` (bench/peaks.json).
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os

from harness.layout import BENCH_DIR


def lookup(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
