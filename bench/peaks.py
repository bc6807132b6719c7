"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

The table is ``peaks.json`` beside this file, with its source. A device
kind that is missing from it is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind, table=TABLE):
    """{"flops_per_s", "hbm_bytes_per_s", "hbm_bytes"} of ``device_kind``."""
    devices = json.loads(Path(table).read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {table}; known: "
                       f"{sorted(devices)}")
    return devices[device_kind]
