"""Host spans and process-wide counters the program keeps for a profiler.

The launch paths mark their phases with :func:`span`.  Nothing records
them until a sink is installed: with none, ``span`` returns one shared
no-op context manager, so the cost of a span is one global read.  A sink
is any callable with the signature ``sink(name, **attrs)`` that returns a
context manager::

    import jax
    from repro import tracing

    jax.profiler.start_trace(out_dir)
    with tracing.installed(jax.profiler.TraceAnnotation):
        exe.run(spikes)          # launch.* spans on the trace's host plane
    jax.profiler.stop_trace()

Counters (:func:`count`, :func:`counts`) are plain integers for events
that tests and the serving pool assert on, such as lowerings.
"""
from __future__ import annotations

import contextlib
from typing import Callable, ContextManager, Dict, Iterator, Optional

Sink = Callable[..., ContextManager]

_sink: Optional[Sink] = None
_counts: Dict[str, int] = {}


#: The span of a process with no sink: enters and exits, records nothing.
_OFF = contextlib.nullcontext()


def active() -> bool:
    """Whether a sink is installed: callers skip attributes that cost more
    to compute than the span (a device array's ``nbytes`` takes about 2 us)."""
    return _sink is not None


def span(name: str, **attrs) -> ContextManager:
    """The phase ``name`` of the caller, with ``attrs`` (small numbers and
    strings), as the installed sink records it; a no-op without one."""
    if _sink is None:
        return _OFF
    return _sink(name, **attrs)


@contextlib.contextmanager
def installed(sink: Sink) -> Iterator[Sink]:
    """Route :func:`span` to ``sink`` inside the block; the previous sink
    (or none) is back afterwards, also when the block raises."""
    global _sink
    previous, _sink = _sink, sink
    try:
        yield sink
    finally:
        _sink = previous


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    _counts[name] = _counts.get(name, 0) + n


def counts() -> Dict[str, int]:
    """A copy of every counter counted so far in this process."""
    return dict(_counts)
