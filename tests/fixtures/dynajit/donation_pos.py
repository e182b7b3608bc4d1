"""DJ3xx positives: use-after-donate, stale donated attribute,
undeclared donation on a KV-pool parameter."""

import jax


def use_after_donate(buf, x):
    step = jax.jit(lambda b, v: b + v, donate_argnums=(0,))
    out = step(buf, x)
    return buf.sum() + out  # DJ301: buf was retired by the call


class Engine:
    def _build_step(self):
        return jax.jit(lambda kv, t: (kv + t, t), donate_argnums=(0,))

    def __init__(self):
        self.cache = None
        self._step = self._build_step()

    def step(self, tokens):
        fn = self._build_step()
        out = fn(self.cache, tokens)  # DJ302: donated attr not rebound
        return out


def kernel_no_declaration(kv_cache, idx):
    return kv_cache[idx]


WRAPPED = jax.jit(kernel_no_declaration)  # DJ303: kv param, no donate kw
