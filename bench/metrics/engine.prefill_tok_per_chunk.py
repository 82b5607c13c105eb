"""Real prompt tokens per prefill chunk: deltas of the engines'
``prefill_tokens`` over ``prefill_chunks`` across the window."""


def read(ctx):
    chunks = ctx.delta("prefill_chunks")
    return ctx.delta("prefill_tokens") / chunks if chunks else None
