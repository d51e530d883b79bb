"""Multi-process streaming edge: shared-memory slab ingest plane.

Wire decode/encode on one event-loop thread caps *served* throughput
far below what the device ticks (PERF.md, "Where the time goes").  This
package is the scaling seam: N edge worker **processes** decode fastwire
streams into columnar REQ32 slabs living in
``multiprocessing.shared_memory``; the device-owner process drains the
published slab windows straight into the existing tick loop (the flat
slot-sorted matrix already supports multi-producer concat) and fans the
response matrices back through per-worker shm response rings.  No pickling, no sockets between decode
and device — the only cross-process traffic is the slab handoff.

Layout and lifecycle live in :mod:`gubernator_tpu.edge.shmring`; the
child process main (no jax import) in :mod:`gubernator_tpu.edge.worker`;
the owner-side drain/supervisor in :mod:`gubernator_tpu.edge.plane`.
See docs/edge.md for topology, crash semantics and backpressure.
"""

from gubernator_tpu.edge.plane import EdgeConfig, EdgePlane

__all__ = ["EdgeConfig", "EdgePlane"]
