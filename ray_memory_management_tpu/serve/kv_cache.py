"""Paged KV-cache page pool for the serve engine.

The monolithic engine cache reserved ``max_slots x max_seq`` KV
positions in HBM up front — a replica serving short requests paid the
full worst case forever, and the only failure mode past that budget was
an allocator OOM. This module is the paged replacement (the vLLM paged-
attention memory-management idea, TPU-shaped): a slot's KV rows are
allocated in pages of ``kv_page_tokens`` positions from a per-replica
pool, held as **pinned device objects** in a dedicated
:class:`~..core.device_store.DeviceObjectStore` so the HBM they occupy
is first-class observable (``rmt_device_bytes_pinned`` /
``rmt_serve_kv_pages_in_use`` move with every reserve/free):

  - :meth:`reserve` claims the pages a request's full lifetime needs
    (prompt + token budget, page-aligned) at admission time; a ``False``
    return is the engine's admission-backpressure signal — the request
    stays queued until a retiring slot frees pages. The pool NEVER
    overcommits, so decode can never hit an allocation failure mid-
    request.
  - :meth:`put_row` / :meth:`take_row` move a slot's live KV arrays in
    and out of the device store between engine iterations; ``take_row``
    uses the store's consume path (``take``) so the engine owns the sole
    reference and can donate the buffers into its compiled step
    (``donate_argnums`` aliases them instead of copying).
  - :meth:`free` at retire deletes the slot's KV objects and returns its
    pages — HBM held by a replica's cache scales with LIVE tokens, not
    with ``max_slots x max_seq``.

The pool's budget is enforced by page accounting, not by store
eviction: the backing store runs with eviction disabled (demoting a
live KV page to host shm would break the donation contract and stall
decode); pressure surfaces as queueing, never as data movement.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from ..core.device_store import DeviceObjectStore


def row_token_bytes(cfg) -> int:
    """HBM bytes one KV position of one slot occupies (k + v across all
    layers)."""
    import jax.numpy as jnp

    itemsize = jnp.dtype(cfg.dtype).itemsize
    return 2 * cfg.n_layers * cfg.kv_heads * cfg.head_dim * itemsize


class KVPagePool:
    """Page-granular KV allocator over a device-object store.

    ``pool_bytes <= 0`` sizes the pool to the monolithic slab it
    replaces (``max_slots x max_seq`` positions), so the paged engine
    can never hold more HBM than the old design's constant footprint.
    """

    def __init__(self, cfg, max_slots: int, page_tokens: int,
                 pool_bytes: int = 0,
                 store: Optional[DeviceObjectStore] = None):
        self.cfg = cfg
        self.page_tokens = max(1, int(page_tokens))
        self.token_bytes = row_token_bytes(cfg)
        self.page_bytes = self.page_tokens * self.token_bytes
        if pool_bytes and pool_bytes > 0:
            budget = int(pool_bytes)
        else:
            budget = max_slots * cfg.max_seq * self.token_bytes
        self.capacity_pages = max(1, budget // self.page_bytes)
        # eviction disabled: the pool budget is enforced by page
        # accounting and admission backpressure, never by demotion
        self.store = store if store is not None else \
            DeviceObjectStore(capacity_bytes=-1)
        self._lock = threading.Lock()
        self._row_pages: Dict[int, int] = {}  # guarded-by: _lock
        self._peak_store_bytes = 0  # guarded-by: _lock

    # -- accounting -----------------------------------------------------------
    def pages_for(self, tokens: int) -> int:
        return max(1, -(-int(tokens) // self.page_tokens))

    def round_tokens(self, tokens: int) -> int:
        """Page-align a token count (a slot's reserved KV capacity)."""
        return self.pages_for(tokens) * self.page_tokens

    def reserve(self, row: int, tokens: int) -> bool:
        """Claim the pages ``row`` needs for ``tokens`` KV positions.
        False = pool exhausted (admission backpressure)."""
        need = self.pages_for(tokens)
        with self._lock:
            in_use = sum(self._row_pages.values()) \
                - self._row_pages.get(row, 0)
            if in_use + need > self.capacity_pages:
                return False
            self._row_pages[row] = need
        self._publish()
        return True

    def free(self, row: int) -> None:
        """Return ``row``'s pages and drop its KV objects (the retire
        path: the gauges fall by exactly this slot's live footprint)."""
        with self._lock:
            self._row_pages.pop(row, None)
        self.store.delete(self._oid(row, "k"))
        self.store.delete(self._oid(row, "v"))
        self._publish()

    def free_all(self) -> None:
        with self._lock:
            rows = list(self._row_pages)
            self._row_pages.clear()
        for row in rows:
            self.store.delete(self._oid(row, "k"))
            self.store.delete(self._oid(row, "v"))
        self._publish()

    # -- KV row movement ------------------------------------------------------
    def put_row(self, row: int, cache: Dict[str, Any]) -> None:
        """Pin a slot's live KV arrays in the device tier (between
        engine iterations the store is the owner)."""
        koid, void = self._oid(row, "k"), self._oid(row, "v")
        self.store.put(koid, cache["k"])
        self.store.put(void, cache["v"])
        self.store.pin(koid)
        self.store.pin(void)
        pinned = self.store.total_bytes()
        with self._lock:
            self._peak_store_bytes = max(self._peak_store_bytes, pinned)

    def take_row(self, row: int) -> Optional[Dict[str, Any]]:
        """Consume a slot's KV arrays out of the store (donation read:
        the engine gets the sole reference and feeds the buffers to its
        ``donate_argnums`` step)."""
        k = self.store.take(self._oid(row, "k"))
        v = self.store.take(self._oid(row, "v"))
        if k is None or v is None:
            return None
        return {"k": k, "v": v}

    # -- introspection --------------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return sum(self._row_pages.values())

    def row_tokens(self, row: int) -> int:
        with self._lock:
            return self._row_pages.get(row, 0) * self.page_tokens

    def bytes_in_use(self) -> int:
        return self.pages_in_use * self.page_bytes

    def stats(self) -> Dict[str, int]:
        with self._lock:
            pages = sum(self._row_pages.values())
            peak = self._peak_store_bytes
        return {
            "page_tokens": self.page_tokens,
            "page_bytes": self.page_bytes,
            "capacity_pages": self.capacity_pages,
            "pages_in_use": pages,
            "bytes_in_use": pages * self.page_bytes,
            "store_bytes": self.store.total_bytes(),
            "peak_store_bytes": peak,
        }

    @staticmethod
    def _oid(row: int, part: str) -> bytes:
        return f"serve.kv.{part}.{row}".encode()

    def _publish(self) -> None:
        try:
            from ..core import metrics_defs as mdefs

            mdefs.serve_kv_pages_in_use().set(float(self.pages_in_use))
        except Exception:  # noqa: BLE001 — gauges never fail the pool
            pass


__all__ = ["KVPagePool", "row_token_bytes"]
