"""Paged cache: share of the pool's tokens that live sequences hold, as
the client can count them: a request between its first token and the close
of its stream holds its prompt plus the tokens streamed so far; the mean
over the window, sampled every 50 ms, over pages x page size.

Not `dynamo_kv_usage_ratio`: that gauge counts pages the prefix cache
keeps after their sequence has gone, and reads 93-95% on prompts that
share nothing (my chip runs, PR 25). A sequence still in prefill holds
pages too and is not counted: this is a floor."""


def read(ctx: dict):
    window, serve = ctx["window"], ctx["config"]["serve"]
    pool_tokens = serve["num_pages"] * serve["page_size"]
    if not ctx.get("timelines") or pool_tokens <= 0:
        return None
    live = ctx["stats"].mean_live_decode_tokens(
        ctx["timelines"], window["t0"], window["t0"] + window["seconds"])
    return 100.0 * live / pool_tokens
