"""The program's own trace points: named scopes and a compile counter.

Named scopes (``jax.named_scope``) put a sub-layer's name into the
``op_name`` of every HLO operation traced under it, so a profiler
trace can say which sub-layer of the federated round each device op
belongs to.  They are trace-time metadata: the compiled program does
the same work with or without them.  No scope may enclose a Pallas
call of the fused forward or the plan backward, nor the ``custom_vjp``
around them: those kernels' HLO instruction names come from the name
stack they are traced under, and readers find them by those names.

The compile counter is a ``jax.monitoring`` listener, installed once
(``install``; importing ``repro.train`` does it), that keeps each
tracing, lowering, backend-compile and persistent-cache-retrieval
event as ``(event, start_ns, end_ns)`` on the wall clock
(``time.time_ns``) that the profiler stamps its host events with.
"""

from __future__ import annotations

import collections
import time

import jax

# -- scope names, one per sub-layer of the federated round -------------
# the local step outside the kernels: the draw word, the clipped scores
# the kernel reads, the MLP forward and, transposed, its backward
FED_MODEL = "fed.model"
FED_UPDATE = "fed.update"  # the optimizer step on the trainable copy
FED_UPLOAD = "fed.upload"  # the end-of-round upload draw and lane pack
FED_AGGREGATE = "fed.aggregate"  # the server reduction over the clients
FED_DOWNLINK = "fed.downlink"  # the broadcast encode and the client decode
QZ_LAYOUT = "qz.layout"  # re-layout of the kernels' operands and results

SCOPES = (FED_MODEL, FED_UPDATE, FED_UPLOAD, FED_AGGREGATE, FED_DOWNLINK,
          QZ_LAYOUT)

# -- compile counter ----------------------------------------------------
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
EVENTS = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT, CACHE_EVENT)

# tracing the full-size federated round alone makes about 10,000
# records (each jitted helper traces inside the outer trace); past the
# bound the oldest go first
_RECORDS = collections.deque(maxlen=1 << 16)
_installed = False


def _listen(event: str, duration_secs: float, **_kwargs) -> None:
    if event in EVENTS:
        end = time.time_ns()
        _RECORDS.append((event, end - round(duration_secs * 1e9), end))


def install() -> None:
    """Register the compile listener (once per process)."""
    global _installed
    if not _installed:
        jax.monitoring.register_event_duration_secs_listener(_listen)
        _installed = True


def records():
    """The compile records so far, oldest first:
    ``[(event, start_ns, end_ns), ...]``."""
    return list(_RECORDS)
