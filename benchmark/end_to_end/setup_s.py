"""Process start to the first timed query: import, data generation,
the first (tracing, compiling or cache-loading) run of the query and
the warm-up.  Host clock."""


def read(ctx):
    return ctx["setup_s"]
