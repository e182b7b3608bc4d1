"""DJ3xx negatives: the rebind-in-the-same-statement discipline and
explicit donation declarations pass clean."""

import functools

import jax


def rebound(buf, x):
    step = jax.jit(lambda b, v: (b + v, v), donate_argnums=(0,))
    buf, out = step(buf, x)
    return buf.sum() + out


class Engine:
    def _build_step(self):
        return jax.jit(lambda kv, t: (kv + t, t), donate_argnums=(0,))

    def __init__(self):
        self.cache = None

    def step(self, tokens):
        fn = self._build_step()
        args = [self.cache, tokens]
        self.cache, out = fn(*args)  # rebound through the star call
        return out


@functools.partial(jax.jit, donate_argnums=())
def gather(kv_cache, idx):
    return kv_cache[idx]  # read-only intent declared explicitly


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter(kv_cache, idx, blocks):
    return kv_cache.at[idx].set(blocks)
