"""Input documents behind the output blocks written in the window, over the
window's seconds (host clock)."""


def read(ctx):
    return ctx["attempted"] / ctx["window_s"] if ctx["window_s"] > 0 else None
