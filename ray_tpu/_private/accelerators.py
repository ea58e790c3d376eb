"""Accelerator detection + visibility plumbing.

Reference: `python/ray/_private/accelerators/tpu.py:15-58` (GKE/GCE
metadata, TPU_VISIBLE_CHIPS, pod topology env vars) and
`util/accelerators/tpu.py` pod helpers. Detection here is env-var and
jax-based; cloud metadata endpoints are stubbed (zero-egress image).
Asking jax initializes the backend, and a backend that cannot
initialize raises: "no TPU" is an answer only a working backend gives.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from ray_tpu._private.platform import chip_devices

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"   # e.g. "v5p-64"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
TPU_NAME_ENV = "TPU_NAME"


def detect_tpu_chips() -> int:
    """Number of TPU chips visible to this process."""
    visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    return len(chip_devices())


def get_accelerator_type() -> Optional[str]:
    """"v5p-64"-style accelerator type, env or device-kind derived."""
    env = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
    if env:
        return env
    tpus = chip_devices()
    if tpus:
        kind = tpus[0].device_kind.lower().replace(" ", "")
        return f"{kind}-{len(tpus)}"
    return None


def get_pod_name() -> Optional[str]:
    return os.environ.get(TPU_NAME_ENV)


def get_worker_id() -> Optional[int]:
    wid = os.environ.get(TPU_WORKER_ID_ENV)
    return int(wid) if wid is not None else None


def set_visible_chips(chip_ids: List[int]) -> None:
    """Scope a worker process to a chip subset (reference:
    set_current_process_visible_accelerator_ids)."""
    os.environ[TPU_VISIBLE_CHIPS_ENV] = ",".join(str(c) for c in chip_ids)


def accelerator_resources() -> Dict[str, float]:
    """Resource dict contribution for node registration."""
    chips = detect_tpu_chips()
    if chips == 0:
        return {}
    res: Dict[str, float] = {"TPU": float(chips)}
    acc_type = get_accelerator_type()
    if acc_type:
        res[f"accelerator_type:{acc_type}"] = 1.0
    return res
