"""ModelRunner step: cached positions whose keys and values prefill
launches rebuilt from their latents, a layer (growth of
`dynamo_latent_prefill_expand_tokens_total` over the latent layers
served), over the prompt tokens prefilled (growth of
`dynamo_engine_tokens{kind="prefill"}`): what chunked prefill that does
not absorb pays again. 1 where every prompt is prefilled in one launch;
a prompt in n equal chunks reads (n + 1) / 2. None where the program
keeps no such counter (a model without latent layers, or a program
before it)."""

from dtbench import scrapes


def read(ctx: dict):
    window, shapes = ctx["window"], ctx["shapes"]
    if not hasattr(shapes, "latent_layer_bytes"):
        return None
    layers = shapes.sizes(ctx["config"])["layers"]
    return scrapes.ratio(
        scrapes.growth(window, "dynamo_latent_prefill_expand_tokens_total"),
        scrapes.growth(window, "dynamo_engine_tokens", kind="prefill"),
        1.0 / layers)
