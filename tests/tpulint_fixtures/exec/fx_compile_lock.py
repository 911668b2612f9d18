"""compile-under-lock rule fixture: no jax.jit / kernel build inside a
`with lock:` body — compile outside, publish under the lock."""
import threading

import jax

from spark_rapids_tpu.exec.base import named_jit

_LOCK = threading.Lock()
_CACHE = {}


def compiles_under_the_lock(key, builder, cache):
    with _LOCK:
        fn = jax.jit(builder)                   # EXPECT: compile-under-lock
        _CACHE[key] = fn
    with cache._lock:
        fn = cache.get_or_build(key, builder)   # EXPECT: compile-under-lock
    with _LOCK:
        fn = named_jit("filter", builder)       # EXPECT: compile-under-lock
    return fn


def compiles_outside_the_lock(key, builder):
    with _LOCK:
        fn = _CACHE.get(key)
    if fn is None:
        fn = jax.jit(builder)                   # outside: fine
        with _LOCK:
            _CACHE[key] = fn
    return fn
