"""Program spans on the profiler's clock — the program's only span facility.

``with span("kv.put"):`` marks a stretch of host work.  While spans are
off (the default) `span` costs one check of a module-level bool and
returns a shared no-op context.  While they are on, it enters
``jax.profiler.TraceAnnotation("objcache." + name)``, so a `jax.profiler`
trace holds the span on its host plane, on the same clock as the
device's XLA modules: each stretch of device idle time can then be put
down to the innermost span that covers it.  Outside a trace an enabled
span records nothing.

A span times what the code already does; it never syncs the device or
copies.  JAX is imported only by `enable`, so modules that use spans
(`serving/kvstore.py`, `core/`) still import without JAX.

Spans on the served path, by nesting::

    engine.lookup
    engine.restore
        kv.get.fs  kv.get.assemble  engine.restore.to_device
    engine.prefill
        kv.put
            kv.put.fs  kv.put.to_host  kv.put.pack  kv.put.fs
    engine.decode

and at `ObjcacheFS`'s boundary, under whichever of those made the call:
``fs.exists``, ``fs.read``, ``fs.write.stage``, ``fs.write.commit``,
``fs.rename``.
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any, ContextManager

PREFIX = "objcache."

_NOOP = nullcontext()
_on = False
_annotation: Any = None


def span(name: str) -> ContextManager:
    """A context that marks the block as ``objcache.<name>`` while spans
    are on, and the shared no-op while they are off."""
    if not _on:
        return _NOOP
    return _annotation(PREFIX + name)


def enable() -> None:
    """Turn spans on, process-wide: around a `jax.profiler` trace."""
    global _on, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on
