"""Published peaks per chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peak(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``. A chip that is not in
    the table is an error: a share of an unknown peak means nothing."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peak for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
