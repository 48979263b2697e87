"""The engine's spans and scopes, on the profiler's clock.

`span(name, **ids)` marks a stretch of host time as `repro:<name>` in a
`jax.profiler` trace, on the same clock as the device operations; `scope(name)`
names the device operations a traced function emits (`jax.named_scope`: the
name lands in each HLO instruction's `op_name` metadata and nowhere else).

Both are always on.  With no profiler session running an annotation costs
about a microsecond, and nothing is kept here: the profiler holds the spans
and writes them out when its trace stops.  Identity rides as annotation
arguments: `run_batch` spans carry the process-wide `call` number that
`next_call` hands out, pool spans the pool's `tick`; children nest inside
their parent in time on the calling thread.

Host spans: `run_batch` (`call`, `trials`, `rounds`) with `run_batch.prepare`,
`run_batch.dispatch` and `run_batch.wait` inside; `pool.admit`, `pool.evict`,
`pool.step` with `pool.step.dispatch` and `pool.step.drain` (`tick`);
`session.step`; `server.dispatch` and `server.readback`.  Device scopes, one
per group of round hooks (`core/rounds.py`): `sample`, `grad`, `prox`,
`channel`, `refresh`, `client_mean`, `psum`, `accounting`.
"""
from __future__ import annotations

import itertools

import jax

PREFIX = "repro:"
SCOPES = ("sample", "grad", "prox", "channel", "refresh", "client_mean", "psum", "accounting")

_calls = itertools.count(1)


def next_call() -> int:
    """A fresh process-wide call number (`next` on a count is atomic in CPython)."""
    return next(_calls)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """Host span `repro:<name>`; `ids` become its arguments in the trace.  The
    returned annotation takes more arguments while open (`set_metadata`)."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def scope(name: str):
    """Named scope for the device operations traced inside it; also a
    decorator."""
    return jax.named_scope(name)
