"""Paged KV cache for the serve engine: one resident pool, a block table.

The cache of a replica is the device arrays its model's cache specification
names (``models.serving_model(cfg).cache_spec(cfg)``: name -> dims before the
pages, dims after a page's positions, dtype): for the dense decoder K and V
of shape ``[L, Hkv, P, page_tokens, Dh]``, for the latent-attention model one
array ``[L, P, page_tokens, cache_width]``, for the model that attends under
a learned selection two arrays of different depth, ``latent`` ``[L, P,
page_tokens, cache_width]`` and ``index`` ``[L_full, P, page_tokens,
index_head_dim]`` (models/latent_sparse_moe.py); ``P - 1`` pages that requests
reserve and one sink. **Every array has the same pages**: the pool sums the
specification's arrays into one ``token_bytes`` (a layer that keeps nothing
in an array adds nothing to it), sizes ``capacity_pages`` from that sum,
allocates each array with its own leading dimensions over the same ``P`` page
ids, and hands out a page id once, for all of them; the block table, the
reservation, the sink and ``pages_in_use`` know nothing of how many arrays
there are. They are allocated once (:meth:`KVPagePool.allocate`,
by the engine thread before its first admission) and stay where they are:
prefill scatters a prompt's cache into the row's pages, the decode step
writes one position a live row and attends through the block table
(ops/paged_attention.py), both on the donated arrays, and nothing copies the
cache between iterations. The pool's bytes are therefore constant; what
tracks the live requests is its *pages* (``rmt_serve_kv_pages_in_use``).

:class:`KVPagePool` is the allocator, all on the host:

  - :meth:`reserve` claims the page ids a request's whole lifetime needs
    (prompt bucket or prompt + token budget, page-aligned) at admission and
    writes them into the row of the block table; a ``False`` return is the
    engine's admission-backpressure signal — the request stays queued until
    a retiring slot frees pages. The pool NEVER overcommits, so a decode
    step can never run out of pages mid-request.
  - :meth:`free` at retire returns the row's pages to the free list and
    points its table entries back at the sink.
  - the sink is one page beyond the budgeted ``capacity_pages`` that no live
    row ever reads: every unreserved table entry points at it, so the writes
    of idle rows, and of a row that overshoots its budget inside an
    iteration of ``steps_per_iter``, land there and never in another
    request's page.

A model may also name arrays that a *slot* holds whatever its length
(``state_spec(cfg)``: name -> dims before the slots, dims after, dtype; the
hybrid state-space model's recurrent state and convolution tail,
models/hybrid_ssm.py): a **state entry**, ``[lead..., max_slots, trail...]``,
sized by slots and not by tokens. It is allocated with the pages, donated
with them and counted beside them (``state_bytes``, ``store_bytes``); no page
id and no reservation refers to it, since slot ``i``'s entry is row ``i``.
Nobody zeroes it between requests: the prefill of an admission overwrites its
slot's entry whole, with the state of the prompt's last real token (a prompt
that rides the decode step in chunks starts its first chunk from zeros
without reading the entry, and each chunk leaves it the state of its last
real token), and the decode step moves only the entries of live slots. A
model without ``state_spec`` (or with an empty one) gets the pages alone, as
before.

**A strided array.** An entry of the cache specification may name a fourth
item, a stride in positions (models/sparse_linear.py: pooled keys, one a 16
positions): the array then holds ``page_tokens / stride`` entries a page,
``[lead..., P, page_tokens / stride, trail...]``, and counts at a
``stride``-th of a position in ``token_bytes``. Entry ``e`` of page ``p``
stands for positions ``stride * e .. stride * e + stride - 1`` of that page,
so one page id still means the same positions of the same row in every
array. What a model owes the pool for such an array: ``page_tokens`` a whole
number of strides, an entry written into the page of the positions it
stands for (even where what it holds is computed from positions of the page
before it), and an entry read only once what it holds is complete (the
pool zeroes nothing, and a page's entries past a row's positions hold
whatever its last owner left there).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List

import numpy as np


def row_token_bytes(cfg) -> int:
    """HBM bytes one cached position of one slot occupies, over all layers
    and arrays (padding the layout carries counted)."""
    from ..models import serving_model

    return _spec_bytes(serving_model(cfg).cache_spec(cfg))


def _entry(spec_item):
    """(dims before, dims after, dtype, stride in positions) of an entry of
    a specification: a stride of 1 where it names none."""
    lead, trail, dtype, *stride = spec_item
    return lead, trail, dtype, (stride[0] if stride else 1)


def _spec_bytes(spec) -> int:
    """Bytes of one entry (a position, or a slot) over a specification's
    arrays; a strided array's at a ``stride``-th of a position."""
    import jax.numpy as jnp

    total = 0.0
    for item in spec.values():
        lead, trail, dtype, stride = _entry(item)
        total += int(np.prod(lead + trail)) * jnp.dtype(dtype).itemsize \
            / stride
    return int(total)


class KVPagePool:
    """Page-granular KV allocator: a free list of page ids and the block
    table ``int32 [max_slots, ceil(max_seq / page_tokens)]``.

    ``pool_bytes <= 0`` sizes the pool to ``max_slots x max_seq``
    positions (plus the sink page): every slot can then hold a request of
    the model's full length at once.
    """

    def __init__(self, cfg, max_slots: int, page_tokens: int,
                 pool_bytes: int = 0):
        self.cfg = cfg
        from ..models import serving_model

        model = serving_model(cfg)
        self.page_tokens = max(1, int(page_tokens))
        self.spec = model.cache_spec(cfg)
        for name, item in self.spec.items():
            if self.page_tokens % _entry(item)[3]:
                raise ValueError(
                    f"a page of {self.page_tokens} positions holds no whole "
                    f"number of {name!r}'s strides of {_entry(item)[3]}")
        self.token_bytes = _spec_bytes(self.spec)
        self.page_bytes = self.page_tokens * self.token_bytes
        # what a slot holds whatever its length (most models: nothing)
        self.max_slots = int(max_slots)
        self.state_spec = model.state_spec(cfg) \
            if hasattr(model, "state_spec") else {}
        self.state_row_bytes = _spec_bytes(self.state_spec)
        if pool_bytes and pool_bytes > 0:
            budget = int(pool_bytes)
        else:
            budget = max_slots * cfg.max_seq * self.token_bytes
        self.capacity_pages = max(1, budget // self.page_bytes)
        self.sink_page = self.capacity_pages  # the arrays' last page
        self.table_width = -(-cfg.max_seq // self.page_tokens)
        # the engine thread's: it alone reserves, frees and reads the table
        self.table = np.full((max_slots, self.table_width), self.sink_page,
                             np.int32)
        self._lock = threading.Lock()
        self._free: List[int] = list(  # guarded-by: _lock
            range(self.capacity_pages - 1, -1, -1))  # pop() -> lowest id
        self._row_pages: Dict[int, List[int]] = {}  # guarded-by: _lock
        self._array_bytes = 0  # guarded-by: _lock

    # -- the device arrays ----------------------------------------------------
    def allocate(self) -> Dict[str, Any]:
        """The pool's arrays, zeroed: for each name of the model's cache
        specification, dims before + (pages and the sink, page_tokens) + dims
        after; and for each name of its state specification, dims before +
        (max_slots,) + dims after. The caller (the engine) owns them: they
        are donated to every prefill and decode program, and a buffer that is
        donated cannot also be pinned in a store."""
        import jax.numpy as jnp

        pool = {}
        for name, item in self.spec.items():
            lead, trail, dtype, stride = _entry(item)
            pool[name] = jnp.zeros(
                lead + (self.capacity_pages + 1, self.page_tokens // stride)
                + trail, dtype)
        pool.update({name: jnp.zeros(lead + (self.max_slots,) + trail, dtype)
                     for name, (lead, trail, dtype)
                     in self.state_spec.items()})
        with self._lock:
            self._array_bytes = (self.capacity_pages + 1) * self.page_bytes \
                + self.state_bytes
        return pool

    @property
    def state_bytes(self) -> int:
        """The state entries' bytes: every slot's, live or not."""
        return self.max_slots * self.state_row_bytes

    # -- accounting -----------------------------------------------------------
    def pages_for(self, tokens: int) -> int:
        return max(1, -(-int(tokens) // self.page_tokens))

    def round_tokens(self, tokens: int) -> int:
        """Page-align a token count (a slot's reserved KV capacity)."""
        return self.pages_for(tokens) * self.page_tokens

    def reserve(self, row: int, tokens: int) -> bool:
        """Claim the pages ``row`` needs for ``tokens`` KV positions and
        put their ids in its table row. False = pool exhausted (admission
        backpressure)."""
        need = self.pages_for(tokens)
        if need > self.table_width:
            raise ValueError(
                f"{tokens} KV tokens need {need} pages, the block table "
                f"holds {self.table_width} a row")
        with self._lock:
            held = self._row_pages.get(row, [])
            if need > len(self._free) + len(held):
                return False
            self._free.extend(reversed(held))
            pages = [self._free.pop() for _ in range(need)]
            self._row_pages[row] = pages
        self.table[row] = self.sink_page
        self.table[row, :need] = pages
        self._publish()
        return True

    def free(self, row: int) -> None:
        """Return ``row``'s pages (the retire path); what they hold stays
        in the arrays until the next owner's prefill overwrites it."""
        with self._lock:
            self._free.extend(reversed(self._row_pages.pop(row, [])))
        self.table[row] = self.sink_page
        self._publish()

    def free_all(self) -> None:
        with self._lock:
            self._row_pages.clear()
            self._free = list(range(self.capacity_pages - 1, -1, -1))
        self.table[:] = self.sink_page
        self._publish()

    # -- introspection --------------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return self.capacity_pages - len(self._free)

    def row_tokens(self, row: int) -> int:
        with self._lock:
            return len(self._row_pages.get(row, ())) * self.page_tokens

    def stats(self) -> Dict[str, int]:
        with self._lock:
            pages = self.capacity_pages - len(self._free)
            array_bytes = self._array_bytes
        return {
            "page_tokens": self.page_tokens,
            "page_bytes": self.page_bytes,
            "capacity_pages": self.capacity_pages,
            "pages_in_use": pages,
            "bytes_in_use": pages * self.page_bytes,
            # what the slots hold whatever their length (0: no state entry)
            "state_row_bytes": self.state_row_bytes,
            "state_bytes": self.state_bytes,
            # the resident arrays, sink and state included: constant once
            # allocated
            "store_bytes": array_bytes,
            "peak_store_bytes": array_bytes,
        }

    def _publish(self) -> None:
        try:
            from ..core import metrics_defs as mdefs

            mdefs.serve_kv_pages_in_use().set(float(self.pages_in_use))
        except Exception:  # noqa: BLE001 — gauges never fail the pool
            pass


__all__ = ["KVPagePool", "row_token_bytes"]
