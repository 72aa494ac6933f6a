"""Program spans on the JAX profiler's clock.

``enable(True)`` turns spans on; run the program under
``jax.profiler.trace(dir)`` and each span becomes a host event of the
trace, on the same clock as the device's operations, so an idle gap of the
chip can be put down to what the host was doing.  Nothing is kept in
memory: the profiler is the one sink.  Off (the default) ``span`` returns
one shared null context after a flag check.

Every span carries ``window=<index>``: ``window(index)`` opens the
``serve.window`` span and sets the index for the spans its thread opens
inside it; ``carry(fn)`` hands the index on to the thread that runs ``fn``.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = ["enable", "span", "window", "carry"]


class _Null(contextlib.nullcontext):
    """The span while tracing is off; ``set_metadata`` does nothing."""

    def set_metadata(self, **_):
        """Nothing to annotate while tracing is off."""


NULL = _Null()
_on = False
_local = threading.local()


def enable(on: bool) -> None:
    """Turn the program's spans on or off (process-wide)."""
    global _on
    _on = bool(on)


def _current() -> int:
    return getattr(_local, "window", -1)


def _arg(v):
    """A span argument as the trace can hold it: a sequence (of sequences)
    of ids becomes one space-separated string, since a comma ends it."""
    if isinstance(v, (list, tuple)):
        return " ".join(str(_arg(x)) for x in v)
    return v


def span(name: str, **args):
    """A profiler span named ``name`` with ``args`` and the current window."""
    if not _on:
        return NULL
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, window=_current(), **{k: _arg(v) for k, v in args.items()})


class _Window:
    """``serve.window``: a span that also sets its thread's window index."""

    def __init__(self, index: int):
        from jax.profiler import TraceAnnotation

        self.index = index
        self._ann = TraceAnnotation("serve.window", window=index)

    def set_metadata(self, **args):
        """Add arguments known only once the close has run (its request count)."""
        self._ann.set_metadata(**args)

    def __enter__(self):
        self._prev = _current()
        _local.window = self.index
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _local.window = self._prev
        return False


def window(index: int):
    """The ``serve.window`` span of one close; spans opened inside it on
    this thread carry ``window=index``."""
    return _Window(index) if _on else NULL


def carry(fn):
    """``fn``, to run on another thread under the calling thread's window."""
    if not _on:
        return fn
    index = _current()

    def run(*a, **kw):
        prev = _current()
        _local.window = index
        try:
            return fn(*a, **kw)
        finally:
            _local.window = prev

    return run
