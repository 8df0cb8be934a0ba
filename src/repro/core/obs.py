"""Spans and a host-sync counter for the solve loops.

A span is a ``jax.profiler.TraceAnnotation``: it costs a constructor call
when no profiler runs and records only while one does, on the same clock
as the device's ops in the profiler's ``.xplane.pb``. There is no switch
and no exporter of its own — the profiler is the exporter. Names:

* host spans (``lpa()``, and the sharded loop ``dist_solve`` that
  ``lpa(LPAConfig(shards > 1))`` and ``dist_lpa()`` run): ``lpa.solve``
  around a call, ``lpa.iteration`` (``it=``, and ``marks=``: ``mover``
  where the mover marks the frontier, ``segment_max`` where
  ``mark_frontier`` does, ``none``) around each pass,
  ``lpa.dispatch.move`` and
  ``lpa.dispatch.frontier`` around the jitted calls and the small
  programs that feed them, ``lpa.sync.<what>`` around each
  device-to-host read (:func:`sync`);
* device scopes (``jax.named_scope``, in the ops' ``op_name``):
  ``lpa.gather`` (round-0 neighbour labels), ``lpa.relayout`` (window
  re-layout between rounds), ``lpa.fold.r<i>`` (round ``i``'s kernel
  launch), ``lpa.marks`` (the frontier from the round-0 kernel's marks),
  ``lpa.apply`` (the winner scatter and the move epilogue) and
  ``lpa.exchange`` (``dist_lpa``'s collectives); the Pallas kernels are
  named ``lpa_fold``, ``lpa_select``, ``lpa_bm_fold`` and ``lpa_rescan``.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """Host span ``name``; ``meta`` is recorded as its stats."""
    return jax.profiler.TraceAnnotation(name, **meta)


def fold_scope(index: int):
    """Device scope of fold round ``index``'s kernel launch."""
    return jax.named_scope(f"lpa.fold.r{index}")


@dataclasses.dataclass
class SyncCount:
    """Device-to-host reads one solve made."""

    host_syncs: int = 0  # blocks entered through sync()


@contextlib.contextmanager
def sync(name: str, count: SyncCount):
    """Span ``lpa.sync.<name>`` around one device-to-host read and the
    small programs that compute what it reads; counts the read."""
    with span(f"lpa.sync.{name}"):
        count.host_syncs += 1
        yield
