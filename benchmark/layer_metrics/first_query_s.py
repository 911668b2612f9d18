"""Layer: kernel cache + XLA compile.  Host clock around the first run
of the query in the process: compiles on an empty persistent cache,
tracing and cache loads on a full one."""


def read(ctx):
    return ctx["first"]["first_query_s"]
