"""One module per architecture: everything the benchmark has to know of a
model's layers, and the one place under ``chipbench/`` that knows it.

A configuration file names its architecture (key ``architecture``; a file
without the key is the dense GQA decoder) and ``of`` finds the module
``chipbench/architectures/<name>.py`` by that name. Nothing lists
architectures. The harness, the drivers, the tools, the readers and the
tests ask the module and index no architecture's keys themselves.

What a module owes (``dense_gqa.py`` is the one to copy):

``WIDTHS``
    the file's keys that are widths: never in ``reduced``.
``AS_PUBLISHED``
    the keys that equal the ``published`` block's, letter for letter.
``REQUIRED``
    every key a file of this architecture must have at its top level.
``program_config(cfg, **over)``
    the program's configuration object of a file, with overrides.
``init_program_params(key, program_cfg)``
    the program's parameters from ``jax.random.PRNGKey(seed)``.
``program_loss(params, batch, program_cfg, mesh)``
    the program's train loss (train cells only).
``server_class()`` and ``server_kwargs(cfg)``
    the program's server that ``drivers/serve.py`` lays its bridges over,
    and the arguments that make it serve this file's model. The class
    offers what ``LLMServer`` offers: ``generate``, ``stats()``, ``cfg``,
    ``params`` and ``_engine.params``.
``reference()``
    the plain reference's module, which imports nothing of the program:
    ``init_params(key, cfg, dtype=None)``, ``logits(params, tokens, cfg,
    compute)`` and, for a train cell, ``mean_loss(params, batch, cfg,
    compute)``, which ``reference/train.py`` follows.
``cache_token_bytes(cfg)``
    bytes of cache one token holds over all layers: what the serve driver
    sizes the pool from where the mix gives no ``kv_pool_bytes``.
``matmul_params(cfg)``, ``n_params(cfg)``, ``forward_flops(cfg, tokens, attended)``
    counts from shapes. ``forward_flops`` is the FLOPs of the parameters a
    token *uses* (with sparse experts: the experts a token is routed to and
    the shared ones, not every expert that is held), two a multiply-add,
    plus attention over ``attended`` (query, key) pairs; recomputation is
    not counted. ``serve.step_mfu`` and ``train.step_mfu`` are then shares
    of one peak and cannot pass 100%.
``attention_shape(cfg)``
    ``(heads, head size)`` of the attention kernel's operands, for a
    kernel's reader (``readers/flash_roofline.py``).

This module and the architecture modules are the only files under
``chipbench/`` that import ``ray_memory_management_tpu.models`` or
``chipbench.reference.model``; a test holds the seam shut.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

DEFAULT = "dense_gqa"


def of(cfg: Dict[str, Any]):
    """The architecture module of a configuration file's dict."""
    return importlib.import_module(
        "chipbench.architectures." + cfg.get("architecture", DEFAULT))
