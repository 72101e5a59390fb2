"""Output tokens of the requests that completed inside the window, whenever
they were due and whenever the tokens were made, over the window's seconds."""


def read(ctx):
    c, seconds = ctx["client"], ctx.get("seconds")
    return c["output_tokens_completed_in_window"] / seconds if seconds else None
