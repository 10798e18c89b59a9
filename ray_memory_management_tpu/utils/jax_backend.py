"""What this process's jax has created so far, read without creating anything.

A chip belongs to one process at a time, so the runtime has to ask "has this
process opened a backend?" in several places (a zygote about to fork, a train
worker about to join a jax.distributed world) without the question itself
opening one. jax has no public accessor for that; the private dict is read
here, once, and a jax that moves it fails loudly instead of each caller
guessing a different default.
"""

from __future__ import annotations

import sys
from typing import Tuple


def initialized_platforms() -> Tuple[str, ...]:
    """Platforms whose backend this process has created ("cpu", "tpu", ...).
    Empty when jax is not imported or has created none. Never imports jax
    and never creates a backend."""
    if "jax" not in sys.modules:
        return ()
    from jax._src import xla_bridge

    backends = getattr(xla_bridge, "_backends", None)
    if not isinstance(backends, dict):
        raise RuntimeError(
            "jax._src.xla_bridge._backends is gone or is no longer a dict "
            "in this jax; ray_memory_management_tpu.utils.jax_backend must "
            "be ported before the runtime can tell which process holds a "
            "chip")
    return tuple(backends)

