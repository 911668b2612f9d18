"""Layer: kernel cache + XLA compile.  JAX's own count of compile
requests during the first run of the query: the distinct compiled
shapes it needs, the same whether the cache is empty or full."""


def read(ctx):
    return ctx["first"]["compile_requests"]
