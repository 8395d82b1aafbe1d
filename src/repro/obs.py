"""Host spans in the profiler's own trace.

The program marks its seams (channel waits, codec, host<->device copies,
jitted dispatch, the batcher) with :func:`span`. Spans are off by
default: a span site then costs one module-global check and gets a
shared null context back. Switched on with :func:`enable`, a span is a
``jax.profiler.TraceAnnotation``, so a ``jax.profiler`` trace holds it
on the same clock as the device's ops, and each device idle gap can be
put down to what the host was doing in it. There is no buffer or
exporter here: the profiler is the sink, and without a running trace
an enabled span records nothing.

Span names are ``<party>.<site>`` (``master.d2h``, ``member0.recv_wait``,
``serve.batcher.hold``); callers build them once, at set-up, never at
the site. docs/serving.md ("Tracing") lists every span.

Example::

    import jax
    from repro import obs

    obs.enable(True)
    with jax.profiler.trace("/tmp/vfl-trace"):
        job.fit()
"""
from __future__ import annotations

import contextlib
from typing import Any, ContextManager, Optional

_NULL = contextlib.nullcontext()
_on = False
_profiler: Any = None        # jax.profiler, imported on the first enable


def enable(on: bool) -> None:
    """Switch spans on or off for the whole process."""
    global _on, _profiler
    if on and _profiler is None:
        import jax.profiler
        _profiler = jax.profiler
    _on = bool(on)


def span(name: str, step: Optional[int] = None) -> ContextManager:
    """A host span named ``name``; ``step`` (the global training step)
    rides on it as a stat, linking one round's spans across threads."""
    if not _on:
        return _NULL
    if step is None:
        return _profiler.TraceAnnotation(name)
    return _profiler.TraceAnnotation(name, step=step)
