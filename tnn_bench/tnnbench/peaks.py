"""Published peaks of one chip, keyed by ``jax.Device.device_kind``
(``tnn_bench/peaks.json``). A kind that is not in the table is an error, never
a default."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
