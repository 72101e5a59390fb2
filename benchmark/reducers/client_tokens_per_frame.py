"""Output tokens per SSE data frame, counted by the client."""


def read(ctx):
    c = ctx["client"]
    return c["tokens"] / c["frames"] if c["frames"] else None
