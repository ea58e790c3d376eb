"""Traffic kinds. ``kind`` in a traffic file selects
``benchmark/drivers/<kind>.py``, which exposes ``run(run) -> record``;
a new kind of traffic is a new file."""

from __future__ import annotations

import importlib


def load(kind: str):
    if not kind.replace("_", "").isalnum():
        raise ValueError(f"bad traffic kind {kind!r}")
    return importlib.import_module(f"benchmark.drivers.{kind}")
