"""The median time to first token of the requests due in the window, from
when each was due: the client's own number, where it decides nothing."""


def read(ctx):
    return ctx["end_to_end"].get("ttft_p50_ms")
