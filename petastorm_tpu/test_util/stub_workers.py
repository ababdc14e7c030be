"""Trivial workers for pool tests (strategy parity: reference
petastorm/workers_pool/tests/stub_workers.py)."""
import time

from petastorm_tpu.workers_pool.worker_base import WorkerBase


class CoeffMultiplierWorker(WorkerBase):
    """Publishes value * args['coeff']."""

    def process(self, value):
        self.publish_func(value * self.args["coeff"])


class IdentityWorker(WorkerBase):
    def process(self, value):
        self.publish_func(value)


class MultiOutputWorker(WorkerBase):
    """Publishes one result per element of the ventilated list."""

    def process(self, values):
        for v in values:
            self.publish_func(v)


class SilentWorker(WorkerBase):
    """Publishes nothing (tests zero-output accounting)."""

    def process(self, value):
        pass


class ExceptionAtNWorker(WorkerBase):
    """Raises on a specific input value."""

    def process(self, value):
        if value == self.args["bad_value"]:
            raise ValueError(f"poisoned value {value}")
        self.publish_func(value)


class SleepyWorker(WorkerBase):
    def process(self, value):
        time.sleep(self.args.get("sleep_s", 0.05))
        self.publish_func(value)


class WorkerIdWorker(WorkerBase):
    """Publishes which worker processed the item (tests round-robin)."""

    def process(self, value):
        self.publish_func((self.worker_id, value))


class EnvReportWorker(WorkerBase):
    """Publishes the worker process's value of ``os.environ[args['name']]``
    (tests what a spawned worker inherits and what the pool pins)."""

    def process(self, value):
        import os
        self.publish_func(os.environ.get(self.args["name"], "unset"))


class BlobWorker(WorkerBase):
    """Publishes ``args['size']`` bytes per item (fills transport buffers —
    used to test shutdown while producers are blocked on backpressure)."""

    def process(self, value):
        self.publish_func(bytes(self.args["size"]))


class ArrowTableWorker(WorkerBase):
    """Publishes a pyarrow Table of n rows (tests the Arrow IPC serializer)."""

    def process(self, n):
        import pyarrow as pa
        self.publish_func(pa.table({"x": list(range(n))}))
