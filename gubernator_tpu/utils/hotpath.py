"""The ``@hot_path`` marker: per-tick serving-path functions.

A function carrying this decorator is on the dispatch side of the tick
pipeline — it runs for every serving window and must QUEUE device work,
never materialize it.  The decorator is a no-op at runtime (one
attribute write at import); its value is the contract it names:
guberlint rule G001 (``gubernator_tpu/analysis``) rejects device-sync
primitives (``np.asarray`` on device values, ``.item()``,
``block_until_ready``, ``jax.device_get``, ``float()``/``bool()``
scalar materialization) inside marked functions, because one per-tick
host/device round trip is the exact regression the fused-tick
architecture exists to avoid (BASELINE.md; the one-dispatch tests of
tier-1 hold the dispatch *counts*, G001 gates the *source*).

Syncs belong on the resolver side — ``TickHandle.result`` /
``resolve_ticks`` — where many windows amortize one D2H.  Nested
functions defined inside a marked function are NOT checked (they are
deferred callbacks that run elsewhere); host-side numpy work that G001
can't distinguish from a device sync is answered inline with
``# guber: allow-G001(reason)``.
"""

from __future__ import annotations


def hot_path(fn):
    """Mark ``fn`` as per-tick serving-path code (see module docstring)."""
    fn.__guber_hot_path__ = True
    return fn
