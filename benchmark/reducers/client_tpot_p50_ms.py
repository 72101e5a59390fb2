"""The median time per output token of the requests due in the window: the
client's own number, steadier than the 95th percentile that is the metric."""


def read(ctx):
    return ctx["end_to_end"].get("tpot_p50_ms")
