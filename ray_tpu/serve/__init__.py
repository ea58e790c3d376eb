"""ray_tpu.serve — model serving.

Reference: Ray Serve (`python/ray/serve`, SURVEY.md §2.2, §3.5): three
planes — controller actor (deploy/reconcile/autoscale), proxies
(HTTP → handle), replicas (user callables) — plus P2C request routing,
dynamic batching and model composition via deployment handles.
"""

from ray_tpu._private.worker import ChunkRun
from ray_tpu.serve.api import (delete, get_app_handle,
                               get_deployment_handle, run, shutdown,
                               start_http_proxy, status)
from ray_tpu.serve.batching import batch
from ray_tpu.serve.multiplex import (get_multiplexed_model_id, multiplexed)
from ray_tpu.serve.deployment import (Application, AutoscalingConfig,
                                      Deployment, deployment)
from ray_tpu.serve.router import DeploymentHandle, DeploymentResponse
from ray_tpu.serve.schema import run_config

__all__ = [
    "deployment", "Deployment", "Application", "AutoscalingConfig",
    "run", "shutdown", "status", "delete", "get_deployment_handle",
    "get_app_handle", "start_http_proxy",
    "batch", "DeploymentHandle", "DeploymentResponse",
    "multiplexed", "get_multiplexed_model_id", "run_config", "ChunkRun",
]
