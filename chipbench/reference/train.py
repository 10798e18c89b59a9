"""The plain reference of a train cell: the loss of the configuration's
architecture (its reference module's ``mean_loss``), gradients and AdamW by
hand.

``follow`` drives the first steps from the seed on the batches the window's
feed makes, and returns what the comparison reads: each step's loss, the norm
of every leaf's first gradient, and the norm of every leaf's change after the
last step. ``compute``/``state_dtype`` other than f32 make the control (the
nearest precision below the configuration's f32 parameters and moments).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from chipbench import architectures


def adamw_update(p, g, m, v, t, opt: dict):
    """optax.adamw's rule written out: bias-corrected moments, decoupled
    weight decay, all in the leaf's own type."""
    b1, b2 = opt["b1"], opt["b2"]
    g = g.astype(p.dtype)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    m_hat = m / (1 - b1 ** t)
    v_hat = v / (1 - b2 ** t)
    step = m_hat / (jnp.sqrt(v_hat) + opt["eps"]) + opt["weight_decay"] * p
    return (p - opt["learning_rate"] * step).astype(p.dtype), \
        m.astype(p.dtype), v.astype(p.dtype)


def leaf_norms(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in jax.tree_util.tree_leaves_with_path(tree)}


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, opt_json: str, compute: str, dtype: str):
    """The jitted gradient, update and change-norm programs of one configuration,
    compiled once however many seeds follow."""
    cfg, opt = json.loads(cfg_json), json.loads(opt_json)
    model = architectures.of(cfg).reference()

    def grad(params, batch):
        loss, grads = jax.value_and_grad(model.mean_loss)(
            params, batch, cfg, compute)
        return loss, grads, leaf_norms(grads)

    def update(params, m, v, grads, t):
        leaves, treedef = jax.tree.flatten(params)
        out = [adamw_update(p, g, m_, v_, t, opt) for p, g, m_, v_ in zip(
            leaves, treedef.flatten_up_to(grads), treedef.flatten_up_to(m),
            treedef.flatten_up_to(v))]
        return tuple(treedef.unflatten([o[i] for o in out])
                     for i in range(3))

    def change_norms(params, key):
        start = model.init_params(key, cfg, dtype)
        return leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params, start))

    # two programs, not one: with the moments idle beside it the gradient
    # program peaks at 13.5 GiB at the train cell's size, fused it is 15.7
    return (jax.jit(grad), jax.jit(update, donate_argnums=(0, 1, 2, 3)),
            jax.jit(change_norms))


def follow(seed: int, cfg: dict, opt: dict, batches, compute: str = "f32",
           state_dtype=None):
    """Losses, first-gradient norms by leaf, change norms by leaf after
    ``len(batches)`` steps, as floats."""
    dtype = jnp.dtype(state_dtype or cfg["param_dtype"]).name
    grad, update, change_norms = _programs(
        json.dumps(cfg, sort_keys=True), json.dumps(opt, sort_keys=True),
        compute, dtype)
    rng = jax.random.PRNGKey(seed)
    params = architectures.of(cfg).reference().init_params(rng, cfg, dtype)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches):
        loss, grads, gnorm = grad(params, batch)
        params, m, v = update(params, m, v, grads, jnp.float32(i + 1))
        del grads
        losses.append(float(loss))
        if first is None:
            first = {k: float(x) for k, x in gnorm.items()}
    change = {k: float(x) for k, x in change_norms(params, rng).items()}
    del params, m, v
    return {"losses": losses, "grad_norms": first, "change_norms": change}
