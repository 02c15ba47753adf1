"""Profiler spans at the layer boundaries of the hot paths: the
simulation engine, the design-space sweep and transfer training.

A span is a ``jax.profiler.TraceAnnotation`` named ``tao/<layer>.<part>``.
While no profiler runs it costs well under a microsecond on the host;
under ``jax.profiler.trace`` it is an event on the profiler's host
timeline, on the same clock as the device's "XLA Ops" and "XLA Modules"
lines, so every gap in which the device idles can be put down to the span
open at that moment.

Every span carries ``call``: the identifier of the
``StreamingEngine.simulate``, ``TraceSweeper.run`` or ``train_tao_impl``
call it belongs to, drawn from one process-wide counter when the call
opens.  A thread started through ``in_call`` (the prefetch producer) keeps
its starter's call, so its spans name the same call as the consumer's.
Parentage is nesting on one thread.  The spans and what each covers: docs/engine.md, "Spans".
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
from typing import Callable, Iterator

import jax

__all__ = ["call_span", "in_call", "span"]

_CALLS = itertools.count(1)
_CALL: contextvars.ContextVar = contextvars.ContextVar("tao_span_call", default=0)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A span ``tao/<name>`` of the current call (``call=0`` outside one)."""
    return jax.profiler.TraceAnnotation(f"tao/{name}", call=_CALL.get(), **args)


@contextlib.contextmanager
def call_span(name: str, **args) -> Iterator[jax.profiler.TraceAnnotation]:
    """Open a new call and its span ``tao/<name>``.  Yields the span, whose
    ``set_metadata(**args)`` adds arguments known only at the call's end."""
    token = _CALL.set(next(_CALLS))
    try:
        with span(name, **args) as sp:
            yield sp
    finally:
        _CALL.reset(token)


def in_call(fn: Callable) -> Callable:
    """``fn`` bound to the current call, for a thread's ``target``."""
    return functools.partial(contextvars.copy_context().run, fn)
