"""Peaks of the chips the benchmark knows: one file a chip,
``chipbench/peaks/<device_kind with _ for spaces>.json``, with its source. A
device that has no file is an error, not a default.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    path = os.path.join(HERE, device_kind.replace(" ", "_") + ".json")
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        known = sorted(f[:-5] for f in os.listdir(HERE) if f.endswith(".json"))
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; known: {known}. Add "
            "the chip's file to chipbench/peaks/ with its source.") from None
