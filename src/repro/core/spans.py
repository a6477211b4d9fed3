"""Host spans and counters of the program, on the device trace's clock.

``span(name, **args)`` opens ``jax.profiler.TraceAnnotation("repro.<name>")``
while a profiler trace runs, so the span lies on the same timeline as the
device's events, with ``args`` as its stats.  With no trace running it
costs one check.  ``tagged(**args)`` sets arguments that every span opened
inside it on the same thread carries: the spans of one request share its
identifier without one long span, which a trace that starts mid-request
would drop.  ``COUNTERS`` adds up host costs for the whole process in
memory, without a lock on the adding path; readers take the difference of
two snapshots.

jax is imported lazily: ``core`` runs without it, and no profiler can run
in a process that has not imported it.
"""

from __future__ import annotations

import contextlib
import sys
import threading

__all__ = ["COUNTERS", "Counters", "span", "tagged"]


class _Off:
    """What ``span`` yields while no trace runs."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **args) -> None:
        pass


_OFF = _Off()
_local = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported


def _enabled_once_jax_is_in() -> bool:
    """Whether a trace runs; replaced by the profiler's own check as soon
    as jax has been imported."""
    global _annotation, _enabled
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation

    _annotation, _enabled = TraceAnnotation, TraceAnnotation.is_enabled
    return _enabled()


_enabled = _enabled_once_jax_is_in


def span(name: str, **args):
    """Context manager for the host span ``repro.<name>``; its stats are the
    thread's ``tagged`` arguments updated by ``args``.  What it yields takes
    ``set_metadata(**more)`` to add stats known only before it closes."""
    if not _enabled():
        return _OFF
    tags = getattr(_local, "tags", None)
    return _annotation(f"repro.{name}", **({**tags, **args} if tags else args))


@contextlib.contextmanager
def tagged(**args):
    """Every ``span`` opened inside this block on this thread carries ``args``."""
    prev = getattr(_local, "tags", None)
    _local.tags = {**prev, **args} if prev else args
    try:
        yield
    finally:
        _local.tags = prev


class Counters:
    """Named sums.  ``add`` writes to the calling thread's own table and
    takes no lock; ``snapshot`` sums every thread's table under the lock
    that guards their list, and is exact once the adding threads are done
    (a snapshot taken while one adds sees its sum before or after the add)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tables: list[dict[str, int]] = []
        self._local = threading.local()

    def add(self, name: str, value: int = 1) -> None:
        try:
            table = self._local.table
        except AttributeError:
            table = self._local.table = {}
            with self._lock:
                self._tables.append(table)
        table[name] = table.get(name, 0) + value

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            tables = [t.copy() for t in self._tables]
        sums: dict[str, int] = {}
        for t in tables:
            for name, value in t.items():
                sums[name] = sums.get(name, 0) + value
        return sums


#: The process's counters: ``a2ws.tasks``/``a2ws.boundary_ns`` (scheduler,
#: ``core/a2ws.py``), ``serve.launches``/``serve.host_cpu_ns`` (decode
#: launches, ``launch/serve.py::generate``), and
#: ``moe.decode_chosen_layers``/``moe.decode_all_held_layers`` (the MoE
#: layers a traced decode program reads only the chosen experts of, or
#: computes every held expert of: ``models/moe.py::moe_decode``, added once
#: per trace, not per launch).
COUNTERS = Counters()
