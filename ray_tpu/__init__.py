"""ray_tpu: a TPU-native distributed AI framework.

Capability contract of Ray (tasks, actors, objects, placement groups, and the
AI-library surface) re-designed TPU-first: SPMD JAX programs over device
meshes are the unit of accelerator work; the control plane schedules them
gang-wise over hosts; Pallas kernels cover the hot ops; XLA collectives over
ICI replace NCCL.

Public core API parity: reference ``python/ray/__init__.py`` /
``python/ray/_private/worker.py`` (init :1341, get :2736, put :2890,
wait :2955, remote :3343).
"""

from __future__ import annotations

import inspect as _inspect
from typing import Any, Dict, List, Optional, Sequence, Union

from ray_tpu import exceptions
from ray_tpu._private import worker as _worker
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.runtime_context import get_runtime_context
from ray_tpu._private.task_spec import (
    NodeAffinitySchedulingStrategy,
    NodeLabelSchedulingStrategy,
    PlacementGroupSchedulingStrategy,
)
from ray_tpu.actor import (ActorClass, ActorHandle, exit_actor, get_actor)
from ray_tpu.remote_function import ObjectRefGenerator, RemoteFunction

__version__ = "0.1.0"

__all__ = [
    "init", "shutdown", "is_initialized", "remote", "get", "put", "wait",
    "kill", "cancel", "get_actor", "get_runtime_context", "ObjectRef",
    "ObjectRefGenerator", "ActorHandle", "exit_actor", "cluster_resources",
    "available_resources", "nodes", "drain_node", "exceptions", "method",
    "NodeAffinitySchedulingStrategy", "NodeLabelSchedulingStrategy",
    "PlacementGroupSchedulingStrategy",
]


def init(num_nodes: int = 1,
         resources: Optional[Dict[str, float]] = None,
         object_store_memory: int = 2 * 1024 ** 3,
         namespace: Optional[str] = None,
         ignore_reinit_error: bool = False,
         _system_config: Optional[Dict[str, Any]] = None,
         **kwargs) -> "_worker.Runtime":
    """Start the runtime with ``num_nodes`` virtual nodes on this host
    (or join a running cluster with ``address="host:port"``).

    ``_system_config`` overrides flags from the central table
    (``ray_tpu/_private/config.py``, the ray_config_def.h role)."""
    if _worker.global_runtime() is not None:
        if ignore_reinit_error:
            return _worker.global_runtime()
        raise RuntimeError("ray_tpu.init() called twice "
                           "(use ignore_reinit_error=True to allow)")
    from ray_tpu._private.config import apply_system_config
    apply_system_config(_system_config)
    # `ray-tpu attach` exports RAY_TPU_ADDRESS so a bare init() joins
    # the attached cluster (reference: RAY_ADDRESS)
    import os as _os
    if not kwargs.get("address") and _os.environ.get("RAY_TPU_ADDRESS"):
        kwargs["address"] = _os.environ["RAY_TPU_ADDRESS"]
    if _os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        # the driver is the process that owns the chip, and what it
        # compiles there is worth keeping; a process that asked for the
        # CPU (tests, workers) persists nothing
        from ray_tpu._private.platform import enable_compile_cache
        enable_compile_cache()
    return _worker.init_runtime(
        num_nodes=num_nodes, resources_per_node=resources,
        object_store_memory=object_store_memory, namespace=namespace,
        **kwargs)


def shutdown() -> None:
    _worker.shutdown_runtime()


def is_initialized() -> bool:
    return _worker.global_runtime() is not None


def _make_remote(obj, options: Dict[str, Any]):
    if _inspect.isclass(obj):
        return ActorClass(obj, options)
    if callable(obj):
        return RemoteFunction(obj, options)
    raise TypeError("@remote decorates a function or a class, "
                    f"got {type(obj).__name__}")


def remote(*args, **kwargs):
    """``@remote`` / ``@remote(**options)`` decorator for tasks and actors."""
    if len(args) == 1 and not kwargs and (callable(args[0])
                                          or _inspect.isclass(args[0])):
        return _make_remote(args[0], {})
    if args:
        raise TypeError("@remote takes only keyword options")
    return lambda obj: _make_remote(obj, kwargs)


def method(**options):
    """Per-method defaults on actor classes (e.g. num_returns)."""
    def decorator(m):
        m.__ray_tpu_method_options__ = options
        return m
    return decorator


def get(refs: Union[ObjectRef, Sequence[ObjectRef]],
        *, timeout: Optional[float] = None) -> Any:
    rt = _worker.global_worker()
    if isinstance(refs, ObjectRef):
        return rt.get([refs], timeout=timeout)[0]
    if isinstance(refs, (list, tuple)):
        bad = [r for r in refs if not isinstance(r, ObjectRef)]
        if bad:
            raise TypeError(f"get() expects ObjectRefs, got {type(bad[0])}")
        return rt.get(list(refs), timeout=timeout)
    raise TypeError(f"get() expects an ObjectRef or list, got {type(refs)}")


def put(value: Any) -> ObjectRef:
    return _worker.global_worker().put(value)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs")
    return _worker.global_worker().wait(
        list(refs), num_returns=num_returns, timeout=timeout,
        fetch_local=fetch_local)


def kill(actor: ActorHandle, *, no_restart: bool = True) -> None:
    _worker.global_worker().kill_actor(actor._ray_actor_id,
                                       no_restart=no_restart)


def cancel(ref: ObjectRef, *, force: bool = False,
           recursive: bool = True) -> None:
    _worker.global_worker().cancel(ref, force=force, recursive=recursive)


def cluster_resources() -> Dict[str, float]:
    return _worker.global_worker().cluster_resources()


def available_resources() -> Dict[str, float]:
    return _worker.global_worker().available_resources()


def nodes() -> List[Dict[str, Any]]:
    rt = _worker.global_worker()
    out = []
    for info in rt.gcs.nodes.values():
        node = rt.get_node(info.node_id)
        out.append({
            "NodeID": info.node_id.hex(),
            "Alive": info.alive,
            "Draining": bool(node is not None
                             and getattr(node, "draining", False)),
            "Resources": dict(info.resources),
            "Labels": dict(info.labels),
        })
    return out


def drain_node(node_id: Union[str, Any],
               deadline_s: Optional[float] = None,
               reason: str = "preemption") -> bool:
    """Gracefully drain a node (planned departure: preemption notice,
    downscale, maintenance): new placements avoid it, queued tasks
    resubmit elsewhere, primary object replicas and actors migrate off
    proactively, and once its running work finishes it leaves the
    cluster with no reconstruction debt. If ``deadline_s`` (default:
    the ``drain_deadline_s`` flag) expires first, the drain escalates
    into the ordinary node-death path. Returns True if a drain started."""
    return _worker.global_worker().drain_node(node_id,
                                              deadline_s=deadline_s,
                                              reason=reason)
