"""The names of the exchange's layers on the profiler's clock.

Two kinds of marks, each name defined once here:

* a *scope* (:func:`scope`, ``jax.named_scope``) names the device ops
  traced inside it: XLA keeps the name in each HLO instruction's
  ``op_name`` (``jit(step)/shard_map/exchange/pack/...``), so a trace
  reduction can put every op of a compiled step down to its layer.  It
  costs nothing at run time.
* a *span* (:func:`span`, ``jax.profiler.TraceAnnotation``) marks host
  time on the profiler's host plane, on the same clock as the device ops.
  When no profiler runs it is one cheap check; a running profiler keeps
  the spans in memory and writes them when the trace stops.

Scopes, innermost wins:

``exchange``  a strategy's exchange inside its step
``pack``      the packer staging a message or wire buffer
``permute``   the transport moving it (the collectives)
``unpack``    the packer writing arrivals into the ghost rims
``update``    the local 27-point update: the in-place kernel, or the XLA
              path's wrap, stencil and write-back

Spans: ``plan.build`` around a persistent plan's trace, lower and compile
(one inside a run of steps is a recompile); ``plan.start`` around the
dispatch of its compiled executable.
"""

from __future__ import annotations

import contextlib

import jax

EXCHANGE = "exchange"
PACK = "pack"
PERMUTE = "permute"
UNPACK = "unpack"
UPDATE = "update"
#: every scope above
SCOPES = (EXCHANGE, PACK, PERMUTE, UNPACK, UPDATE)

PLAN_BUILD = "plan.build"
PLAN_START = "plan.start"

_IDLE = contextlib.nullcontext()


def scope(name: str):
    """Name the device ops traced inside the block (HLO ``op_name``)."""
    return jax.named_scope(name)


def span(name: str):
    """Mark the block's host time on the profiler's host plane.  With no
    profiler running nothing is built: ``plan.start`` wraps every dispatch,
    the host cost the persistent plan exists to cut."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name)
    return _IDLE
