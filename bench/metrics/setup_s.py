"""Process start to the first output block written (host clock): imports,
corpus, probe, model init, compile or cache load, pipeline fill."""


def read(ctx):
    return ctx["setup_s"]
