"""Names for the profiler: host spans (:func:`span`), device scopes
(:func:`scoped`) and named programs (:func:`program`).

    with span("serve.prefill", uid=r.uid, rows=16):
        ...

While a profiler session records (``jax.profiler.start_trace`` /
``stop_trace``) the block is a ``jax.profiler.TraceAnnotation``: the span
and its keyword args land in the trace's ``.xplane.pb`` on the host thread
that opened it, on the same clock as the device ops.  Otherwise ``span``
returns a shared no-op context and builds nothing, so spans left in the
serving loop cost a function call and one flag read.  The profiler keeps
the spans in memory and writes them when the trace stops.

Device programs get their names from ``jax.named_scope`` instead, which
only sets HLO ``op_name`` metadata and leaves the compiled arithmetic as it
is; :func:`scoped` puts a whole function under one.  A trace names each
run of a program by its module, ``jit_<function name>``; :func:`program`
jits a function under a scope and a module of the same name.
"""
from __future__ import annotations

import contextlib
import functools

import jax
from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A profiler span named ``name`` with ``args`` as its stats while a
    trace records; a shared no-op context otherwise."""
    if not _profiler.TraceMe.is_enabled():
        return _OFF
    return TraceAnnotation(name, **args)


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``.  A
    fresh scope per call: one ``named_scope`` object used as the decorator
    keeps its saved name stack on itself, which a nested or concurrent
    trace of the same function would overwrite."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def program(name: str, fn, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` traced under ``jax.named_scope(name)``
    and compiled as the module ``jit_<name>`` (JAX names the module after
    the function's ``__name__``)."""
    call = scoped(name)(fn)
    call.__name__ = call.__qualname__ = name
    return jax.jit(call, **jit_kwargs)
