"""The table of peaks, keyed by ``device_kind``. A device that is not in
the table is an error, never a default."""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark import manifest


def for_device(kind: str) -> Dict[str, Any]:
    table = manifest.load_json(os.path.join(manifest.HERE, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks on record for device_kind {kind!r}; add it "
                       "to benchmark/peaks.json with its source")
    return table[kind]
