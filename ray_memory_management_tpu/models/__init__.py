"""The models. :func:`serving_model` is how the serve engine finds the one a
configuration object belongs to."""


def serving_model(cfg):
    """The module that serves ``cfg``: it offers ``init_params(key, cfg)``,
    ``cache_spec(cfg)``, ``prefill_row(params, tokens, cfg, n_positions,
    true_len)``, ``prefill_takes_kernel(cfg, n_tokens)`` and
    ``paged_decode(params, tokens, pool, positions, lengths, page_table,
    cfg)`` (models/gpt.py and models/latent_moe.py say what each
    returns), and where a slot holds a state besides its pages also
    ``state_spec(cfg)`` (models/hybrid_ssm.py)."""
    from . import gpt, hybrid_ssm, latent_moe

    for module, kind in ((gpt, gpt.TransformerConfig),
                         (latent_moe, latent_moe.LatentMoEConfig),
                         (hybrid_ssm, hybrid_ssm.HybridSSMConfig)):
        if isinstance(cfg, kind):
            return module
    raise TypeError(f"no model serves a {type(cfg).__name__}")
