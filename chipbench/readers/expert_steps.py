"""What an expert model's decode step counted of itself inside the window:
``after`` - ``before`` of the counts ``stats()["engine"]`` carries for it
(``expert_tokens`` [E], ``experts_touched``, ``expert_layer_steps``, beside
the engine's ``iterations``, ``slab_positions``, ``live_positions`` and
``cache_token_bytes``), as means a token-step, and the traced token-steps of
the decode program. Shared by the two roofline readers of the step and of
the latent kernel; a program without the counts reads ``None``.

The two readers join two clocks: the counters are the whole window's, the
trace is its last seconds. Means a token-step come from the counters,
seconds and calls from the trace. A closed loop is steady, so the two agree;
a share over 100% is a fault of the count and is never clipped."""

from chipbench.readers import engine_window as ew

KERNEL = "latent_decode_attention"   # the pallas_call's name
PROGRAM = "jit_paged_step_fn"        # the engine's one decode program


def window(ctx):
    """Means a decode token-step over the window, or None: live ``rows``,
    cached ``positions`` they attend over (``fetched``: with the tail of
    each row's last page), ``experts_touched`` a mean expert layer."""
    pair = ew.engines(ctx)
    if pair is None or not pair[1].get("expert_layer_steps"):
        return None
    b, a = pair
    d = lambda k: a[k] - b.get(k, 0)  # noqa: E731
    cfg = ctx["cfg"]
    sparse = cfg["num_hidden_layers"] - min(cfg["first_k_dense_replace"],
                                            cfg["num_hidden_layers"])
    steps, its = d("expert_layer_steps") / max(1, sparse), d("iterations")
    if steps <= 0 or its <= 0:
        return None
    tokens = sum(a["expert_tokens"]) - sum(b.get("expert_tokens", [0]))
    return {"rows": tokens / cfg["num_experts_per_tok"]
            / d("expert_layer_steps"),
            "positions": d("live_positions") / its,
            "fetched": d("slab_positions") / its,
            "experts_touched": d("experts_touched")
            / d("expert_layer_steps"),
            "token_steps": steps}


def kernel(ctx):
    """(calls, device seconds) of the latent decode kernel in the trace."""
    t = ctx.get("trace")
    if not t or not t.get("ops"):
        return 0, 0.0
    # by the operation's own name: the text of an operation that reads the
    # kernel's result names the kernel too
    mine = [k for k in t["ops"] if KERNEL in k]
    return (sum(t["op_calls"][k] for k in mine),
            sum(t["ops"][k] for k in mine))
