"""A stand-in for a configuration's own byte and operation counts, for the
tests of the seam only: dtbench/shapes.py's interface over numbers the
configuration's file gives under `counts`, so that a reader's result
shows which module it called. Imports nothing but Python."""


def weight_bytes_per_step(cfg):
    return float(cfg["counts"]["weight_bytes"])


def kv_bytes_per_token(cfg):
    return float(cfg["counts"]["latent_bytes_per_token"])


def decode_step_bytes(cfg, live_tokens):
    return weight_bytes_per_step(cfg) + live_tokens * kv_bytes_per_token(cfg)


def attention_step_bytes(cfg, live_tokens):
    return live_tokens * kv_bytes_per_token(cfg)


def flops_per_token(cfg, context):
    return 2.0 * cfg["counts"]["params_per_token"] + context
