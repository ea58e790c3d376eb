"""Traffic kinds. ``kind`` in a traffic file selects
``benchmark/drivers/<kind>.py``, which exposes ``run(run) -> record``;
a new kind of traffic is a new file."""

from __future__ import annotations

import importlib


class MisSized(RuntimeError):
    """The cell's traffic does not fit the system it met (a closed-loop
    window that would last a few seconds): the run fails by this
    message and prints no result."""


def load(kind: str):
    if not kind.replace("_", "").isalnum():
        raise ValueError(f"bad traffic kind {kind!r}")
    return importlib.import_module(f"benchmark.drivers.{kind}")
