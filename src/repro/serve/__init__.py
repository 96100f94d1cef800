"""Durable continuous-batching serving subsystem over the CXL0 tier stack.

Three layers, one per module:

* ``serve.scheduler`` — slot-based continuous batching: requests are
  admitted FIFO into fixed decode slots, prefill of new requests
  interleaves with batched decode of running ones, finished sequences
  free their slot immediately (no static-batch stragglers);
* ``serve.kvcache``   — KV-cache lanes: the decode batch's per-slot
  caches in HBM, jitted slot surgery, and device-side reads of a lane's
  dirty token blocks (block geometry from ``serve.paging.BlockPager``);
* ``serve.sessions``  — durable session store: session state (prompt,
  emitted tokens, KV-cache version) commits through the FliT commit path
  (``dsm.flit_runtime.DurableCommitter``), so a killed serving worker
  restarts via ``dsm.recovery`` and resumes every committed session with
  bit-identical continuations.

``serve.engine.ServeEngine`` wires them to the model bundle's prefill +
slot-masked decode steps (``train.step.make_slot_decode_step``);
``serve.trace`` generates the deterministic synthetic request traces the
benchmarks and crash scenarios share.  ``launch/serve.py`` and
``examples/serve.py`` are thin front-ends over this package.
"""
from repro.serve.engine import ServeEngine, ServeResult
from repro.serve.scheduler import Request, SlotScheduler
from repro.serve.trace import synthetic_trace

__all__ = ["ServeEngine", "ServeResult", "Request", "SlotScheduler",
           "synthetic_trace"]
