"""Host spans and host-read counts of the served path (DESIGN.md §2.14).

Always on; there is nothing to switch.  With nothing listening a stage
costs a few microseconds of host time and a host read under one,
against queries of milliseconds to seconds:

* :func:`stage` opens a ``jax.profiler.TraceAnnotation``, so a profiler
  trace shows the stage on the device trace's clock, and on exit records
  the stage's host seconds as the ``jax.monitoring`` duration event
  ``"/repro/" + name``, so an untraced process can still attribute host
  time to stages through a listener.
* :func:`to_host` is the one way the served path reads a device array to
  the host: ``jax.device_get`` (explicit, so legal under the sanitizer's
  transfer guard) plus the ``jax.monitoring`` scalar event
  :data:`HOST_READ_BYTES` carrying the bytes read and a ``site`` tag.
* Each spmd dispatch records the bytes its exchange sent between
  devices as the scalar event :data:`EXCHANGE_BYTES`; placing a graph
  version on the mesh is the stage :data:`PLACE`.
* Building a relax step records the E-long source gathers of one dense
  sweep of its program as the scalar event :data:`SOURCE_GATHERS`.
* The diffusion loop's ``jax.named_scope`` names (:data:`SCOPES`) set
  only op metadata: a device trace or a compiled module attributes each
  device op to the relax sweep, the local apply, the round-end exchange
  or the loop control.  A transform may wrap the name in the metadata
  (``.../vmap(diffuse.relax)/...``); the innermost scope names the op.

The names are module constants so that readers import them rather than
copy the strings.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

# -- host spans (TraceAnnotation names; "/repro/<name>" duration events) ---

EVENT_PREFIX = "/repro/"

QUERY = "repro.query"                    # the whole DiffusionSession.query
QUERY_PREPARE = "repro.query.prepare"    # program, compaction, eager init
QUERY_DISPATCH = "repro.query.dispatch"  # enqueue the fixed-point program
QUERY_BUDGET = "repro.query.budget"      # read `converged`: the host waits
QUERY_RESULT = "repro.query.result"      # to_global, live_ids, validation
QUERY_STAGES = (QUERY_PREPARE, QUERY_DISPATCH, QUERY_BUDGET, QUERY_RESULT)

COMMIT_JOURNAL = "repro.commit.journal"  # write-ahead append and fsync
COMMIT_APPLY = "repro.commit.apply"      # the batched update scatter
COMMIT_REPAIR = "repro.commit.repair"    # + "." + strategy, per cache entry
COMMIT_COMPACT = "repro.commit.compact"  # a restart repair's compaction

PLACE = "repro.place"    # spmd: a graph version's cells onto the mesh

# -- host reads (jax.monitoring scalar event, value = bytes, tag site=) ----

HOST_READ_BYTES = EVENT_PREFIX + "host_read_bytes"

# -- exchange bytes (jax.monitoring scalar event, value = bytes) -----------

# per spmd dispatch: rounds x the bytes one round's all_to_all sends
# between devices, from shapes (diffuse.spmd_exchange_bytes)
EXCHANGE_BYTES = EVENT_PREFIX + "exchange_bytes"

# -- source gathers (jax.monitoring scalar event, tag program=) ----------

# per relax step built (relax.make_relax): the E-long gathers of the
# source vertex one dense sweep makes (programs.SourceReads.gathers)
SOURCE_GATHERS = EVENT_PREFIX + "source_gathers"

# -- device scopes (jax.named_scope, in each op's op_name metadata) --------

SCOPE_RELAX = "diffuse.relax"        # gather -> emit -> segment-combine
SCOPE_APPLY = "diffuse.apply"        # outbox merge, on_send, local receive
SCOPE_EXCHANGE = "diffuse.exchange"  # round-end delivery, replica merge
SCOPE_CONTROL = "diffuse.control"    # gate, bucket, quiescence, stats
SCOPES = (SCOPE_RELAX, SCOPE_APPLY, SCOPE_EXCHANGE, SCOPE_CONTROL)


@contextlib.contextmanager
def stage(name: str):
    """A named host stage: a profiler span, and its host seconds as the
    duration event ``EVENT_PREFIX + name``."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            jax.monitoring.record_event_duration_secs(
                EVENT_PREFIX + name, time.perf_counter() - t0)


def to_host(x, site: str):  # analysis: allow(host-sync): the counted host read; callers read per query or per op, never per round
    """``x`` as a host array; a device array is read with
    ``jax.device_get`` and counted as a :data:`HOST_READ_BYTES` event
    tagged ``site``.  Host arrays pass through uncounted."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    out = jax.device_get(x)
    jax.monitoring.record_scalar(HOST_READ_BYTES, out.nbytes, site=site)
    return out
