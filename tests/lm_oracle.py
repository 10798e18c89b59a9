"""The whole forward's greedy answer: the oracle the engine tests hold a
served answer to."""

import jax.numpy as jnp
import numpy as np


def greedy_answer(forward, cfg, params, prompt, out):
    """What ``forward`` puts first at each position of ``out`` behind
    ``prompt``, in one forward over ``prompt + out[:-1]``. The model is
    causal, so the logits at ``len(prompt) - 1 + i`` are what greedy
    decoding of ``prompt + out[:i]`` sees: the result equals ``out`` where
    ``out`` is the greedy answer."""
    logits = forward(params, jnp.asarray(
        [list(prompt) + list(out[:-1])]), cfg)[0]
    return np.argmax(np.asarray(logits[len(prompt) - 1:]), -1).tolist()
