"""The chip's published peaks, keyed by JAX's ``device_kind``
(``peaks.json``).  A device that is not in the table is an error: a
roofline share against a guessed peak would be a number with no meaning."""

from __future__ import annotations

import json
import os

from benchlib.spec import BENCH_DIR


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: str = os.path.join(BENCH_DIR, "peaks.json")) -> dict:
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device_kind {device_kind!r} in "
                            f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
