"""Placement actuator: the decode stage's thread-vs-process backend as a
tunable knob (cedar's insight, PAPERS.md: an input pipeline is an operator
graph whose *placement* the optimizer chooses — not just its buffer sizes).

The knob is binary — ``0`` = thread pool (in-process, zero transport cost,
GIL-shared), ``1`` = process pool (spawned workers, shm Arrow transport,
GIL-free) — and which side wins is workload- and host-dependent: a
decode-heavy store on a many-core host wants processes; a small store on a
starved host wants threads.
So the controller runs a **measured trial**: when the pipeline stays
producer-bound with every conventional knob maxed, it flips placement,
waits for the migration to apply and a settle window to pass, then compares
delivered rows/sec against the pre-trial baseline — keeping the winner and
pinning the knob (no A/B thrash on a knob whose actuation costs seconds).

Actuation is asynchronous by design: ``_apply`` only *requests* the
migration from the owning Reader; the swap itself happens at the Reader's
consumer-thread safe point (pause ventilation at an item boundary, drain
the old pool's in-flight work, stand up the new pool, repoint the
ventilator) — see ``Reader._perform_pool_migration``. :attr:`applied`
flips once the swap completed; the controller's settle countdown starts
there, not at the request.
"""
from __future__ import annotations

import threading

from petastorm_tpu.autotune.actuators import Actuator

__all__ = ["PlacementActuator", "POOL_BACKENDS"]

#: Actuator value -> reader_pool_type.
POOL_BACKENDS = ("thread", "process")


class PlacementActuator(Actuator):
    """:param migrate_fn: callable ``(backend: str) -> None`` scheduling the
        migration (``Reader._request_pool_migration``)
    :param initial_backend: the pool type the reader started with
    """

    def __init__(self, migrate_fn, initial_backend: str, telemetry=None):
        if initial_backend not in POOL_BACKENDS:
            raise ValueError(f"placement only tunes thread<->process pools, "
                             f"got {initial_backend!r}")
        self._migrate = migrate_fn
        self._applied = threading.Event()
        self._applied.set()  # the initial backend is trivially applied
        #: True when the LAST requested migration aborted (quiesce/drain
        #: timeout, pool-start failure): the controller must cancel — not
        #: measure — the trial built on it.
        self.last_apply_failed = False
        super().__init__("placement", 0, 1,
                         POOL_BACKENDS.index(initial_backend),
                         telemetry=telemetry)

    @property
    def backend(self) -> str:
        return POOL_BACKENDS[self.value]

    @property
    def applied(self) -> bool:
        """True once the last requested migration actually completed (the
        Reader calls :meth:`mark_applied` at the end of the swap)."""
        return self._applied.is_set()

    def mark_applied(self) -> None:
        self.last_apply_failed = False
        self._applied.set()

    def mark_failed(self, live_backend: str) -> None:
        """Migration aborted (quiesce timeout, drain deadline, pool-start
        failure): re-sync the actuator to the backend actually running
        WITHOUT triggering another migration, so the controller's trial
        never measures a swap that did not happen and the
        ``autotune.placement`` gauge stays truthful."""
        value = POOL_BACKENDS.index(live_backend)
        with self._lock:
            self._value = value
        if self._gauge is not None:
            self._gauge.set(value)
        self.last_apply_failed = True
        self._applied.set()

    def _apply(self, value: int) -> None:
        self._applied.clear()
        self._migrate(POOL_BACKENDS[value])
