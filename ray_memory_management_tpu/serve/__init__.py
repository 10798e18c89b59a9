"""Serve library: online model serving over the actor runtime.

The reference's ``ray.serve`` (python/ray/serve/ — controller actor,
deployment/replica reconciler, router with in-flight caps, long-poll
config push, autoscaling, HTTP proxies).
"""

from .api import (  # noqa: F401
    delete,
    get_deployment_handle,
    get_handle,
    list_deployments,
    run,
    shutdown,
    start,
    status,
)
from .deployment import (  # noqa: F401
    Application,
    AutoscalingConfig,
    Deployment,
    deployment,
)
from .handle import (  # noqa: F401
    BackpressureTimeout,
    DeploymentHandle,
    NoReplicasError,
)
from .kv_cache import KVPagePool  # noqa: F401
from .llm import (  # noqa: F401
    ContinuousBatcher,
    LLMServer,
    llm_deployment,
    pack_weights,
    unpack_weights,
)
