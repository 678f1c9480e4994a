"""Named host spans of the split-serving path, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation(name, **ids)``.  With a
profiler attached (``jax.profiler.trace`` or ``start_trace``) it lands
in the trace's host plane, on the same clock as the device planes, with
``ids`` as its metadata; with none attached it records nothing and
costs about a microsecond.  The profiler is the only switch.

Every span carries ``seq``, the session's micro-batch seq (-1 where the
thread holds no micro-batch), so the spans of one micro-batch share it.
Stage spans also carry ``stage`` (the worker's name) and hop spans
``hop`` (the hop's index).  A name ending in ``_wait`` marks waiting,
not work.

  gateway.admit        Gateway._admit: gather, concat, pad, submit
  gateway.deliver      Gateway._advance: split one host copy into the
                       requests' rows, meters, QoS records
  gateway.fetch        Gateway._deliver: the micro-batch's one copy to
                       the host; ``requests`` is how many it serves
  session.result_wait  Session._pump: blocked on the pipeline
  stage.recv_wait      a stage thread waiting for its input
  stage.dispatch       Worker.run: the program launch, with the upload
                       of a host input
  stage.sync_wait      Worker.run: blocked until the device is done
  hop.d2h              EmulatedChannel: the copy off the device, coded
                       hops only (an uncoded tensor crosses as the
                       device array)
  hop.encode           EmulatedChannel: the codec's packing
  hop.decode           EmulatedChannel: the codec's unpacking
  hop.put_wait         EmulatedChannel.send: blocked on a full hop queue

A stage thread names the micro-batch it works on with ``set_seq``; the
stage program and the hop it then calls read it with ``current_seq``.
"""
from __future__ import annotations

import threading

_local = threading.local()


def set_seq(seq: int) -> None:
    """Name the micro-batch this thread works on: the seq that its stage
    and hop spans carry, until the next call."""
    _local.seq = seq


def current_seq() -> int:
    """The micro-batch this thread works on (``set_seq``), or -1."""
    return getattr(_local, "seq", -1)
