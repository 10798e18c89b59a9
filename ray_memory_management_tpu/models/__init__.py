"""The models. :func:`serving_model` is how the serve engine finds the one a
configuration object belongs to."""


def serving_model(cfg):
    """The module that serves ``cfg``: it offers ``init_params(key, cfg)``,
    ``cache_spec(cfg)``, ``prefill_row(params, tokens, cfg, n_positions,
    true_len)``, ``prefill_takes_kernel(cfg, n_tokens)`` and
    ``paged_decode(params, tokens, pool, positions, lengths, page_table,
    cfg)`` (models/gpt.py and models/latent_moe.py say what each
    returns), where a slot holds a state besides its pages also
    ``state_spec(cfg)`` (models/hybrid_ssm.py), and, optionally,
    ``mixed_step`` (below). A model whose layers are of several kinds gives
    both specifications **by kind** (models/nemotron_h.py): the leading
    dimension of ``cache_spec``'s arrays counts the layers that leave
    something in a page, that of ``state_spec``'s the layers that keep a
    state, and a layer that does neither appears in none; the pool takes
    any leading dimensions (serve/kv_cache.py). The optional
    ``mixed_step(params, pool, chunk_tokens, chunk_pages, chunk_last,
    tokens, positions, lengths, page_table, cfg, *, chunk_index)``: one
    chunk of a prompt and one decode token a live row in one pass over the
    layers (models/gpt.py). ``chunk_last`` is the position inside the chunk
    whose logits are wanted **and the last of the chunk's real positions**:
    the prompt's last token in its last chunk, the chunk's last position in
    every other. A model with ``state_spec`` is also handed ``slot=``, the
    index of the row being prefilled: its first chunk (``chunk_index`` 0)
    starts from zeros whatever the slot's entry holds, a later one from the
    entry, and each leaves there the state as of its last real position;
    the row is idle among the decode rows, so only its chunks move its
    entry (models/hybrid_ssm.py). A model whose layers are of several kinds
    walks its pattern in ``mixed_step`` as in ``paged_decode``, each kind
    splitting the ``C + B`` rows its own way (models/nemotron_h.py: a
    state layer as above, an attention layer as the dense decoder's, an
    expert layer in one call over the chunk's real positions and the live
    rows together). What ``mixed_step`` counts of itself it returns under
    names of its own (``mixed_*``), never under ``paged_decode``'s: the
    engine adds counts up by name, and a mean a decode token-step stays
    the decode program's. The engine prefills in chunks that ride
    its decode steps where a model offers it, and whole prompts through
    ``prefill_row`` where it does not (a model that offers ``mixed_step``
    is never asked for ``prefill_row``, but is still asked
    ``prefill_takes_kernel`` for its chunk).

    **A model with two paged arrays of different depth**
    (models/latent_sparse_moe.py: a latent vector in every layer, an index
    key in the layers that have an indexer) names both in ``cache_spec``,
    each with its own leading dimension and width; what it owes the pool is
    that **one page id means the same positions of the same row in every
    array**, so that one block table, one reservation and one sink serve
    both: it indexes each array by its own count of layers (entry ``j`` of
    the shallower array is the ``j``-th layer of its kind), writes a
    position into every array that holds it before any of its layers reads
    it back, and never reads an array at a layer that does not keep one.

    **A strided array** (models/sparse_linear.py: a pooled key a 16
    positions beside the keys and values) names its stride in positions as
    a fourth item of its ``cache_spec`` entry; a page then holds ``page_tokens
    / stride`` of its entries, entry ``e`` standing for the page's positions
    from ``stride * e`` on. Beside the rule above the model owes the pool to
    write such an entry into the page of the positions it stands for, and to
    read it only once it is complete (serve/kv_cache.py)."""
    from . import (gpt, hybrid_ssm, latent_moe, latent_sparse_moe, nemotron_h,
                   sparse_linear)

    for module, kind in ((gpt, gpt.TransformerConfig),
                         (latent_moe, latent_moe.LatentMoEConfig),
                         (hybrid_ssm, hybrid_ssm.HybridSSMConfig),
                         (nemotron_h, nemotron_h.NemotronHConfig),
                         (latent_sparse_moe,
                          latent_sparse_moe.LatentSparseMoEConfig),
                         (sparse_linear, sparse_linear.SparseLinearConfig)):
        if isinstance(cfg, kind):
            return module
    raise TypeError(f"no model serves a {type(cfg).__name__}")
