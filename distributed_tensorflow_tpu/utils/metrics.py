"""Metrics/observability: the reference's stdout format + scalar sinks.

The reference's observability is the cadenced print
(``MNISTDist.py:183-186``) and a summary op wired into the Supervisor's
event files (``:155,162`` — though it merges nothing, SURVEY.md §5). Here
the same stdout line is reproduced verbatim-format, and every scalar lands
in BOTH a JSONL file (any plotting tool) and a TensorBoard event file
(utils/events.py — the summary-writer parity path)."""

from __future__ import annotations

import json
import math
import os
import threading
import time

from distributed_tensorflow_tpu.utils.events import EventFileWriter


def reference_log_line(job_name: str, task_index: int, step: int, loss, acc) -> str:
    """The exact print of MNISTDist.py:183-186 (print-function comma
    semantics: single-space join of the arguments)."""
    return " ".join(
        [
            f"job: {job_name}/{task_index}",
            "step: ",
            str(step),
            "mini_batch loss: ",
            str(loss),
            "training accuracy: ",
            str(acc),
        ]
    )


class MetricsLogger:
    """Scalar logger: stdout (reference format) + JSONL + TB event file.

    Thread-safe: the serving metrics cadence (batcher worker threads)
    and a training loop can share one logger — ``scalars`` serializes
    the two sink writes under a lock so JSONL lines and event frames
    never interleave. Every emission also rides the telemetry flight
    ring, so a crash postmortem shows the last scalars next to the last
    spans; ``flush()`` (called at the display cadence and from the
    flight-recorder dump path) pushes both sinks' buffered tails to
    disk so a crash doesn't lose them."""

    def __init__(self, logdir: str | None = None, job_name: str = "worker",
                 task_index: int = 0, filename: str = "metrics.jsonl"):
        self.job_name = job_name or "worker"
        self.task_index = task_index
        self._file = None
        self._events = None
        self._lock = threading.Lock()
        if logdir:
            os.makedirs(logdir, exist_ok=True)
            self._file = open(os.path.join(logdir, filename), "a", buffering=1)
            self._events = EventFileWriter(logdir)
            # flight-recorder dumps flush this logger's tails too
            from distributed_tensorflow_tpu.utils import telemetry

            telemetry.register_flush(self.flush)

    def log_display(self, step: int, loss, acc, counters=None):
        """The reference's display line, and the display row; ``counters``
        (a model's own display metrics: the routed layer's rows an expert,
        the diffusion objective's masked share) ride in the same row."""
        print(reference_log_line(self.job_name, self.task_index, step, loss, acc))
        self.scalars(step, {"mini_batch_loss": float(loss),
                            "training_accuracy": float(acc),
                            **(counters or {})})

    def scalars(self, step: int, values: dict):
        from distributed_tensorflow_tpu.utils import telemetry

        with self._lock:
            if self._file is not None:
                rec = {"step": int(step), "time": time.time(),
                       "job": f"{self.job_name}/{self.task_index}", **values}
                self._file.write(json.dumps(rec) + "\n")
            if self._events is not None:
                self._events.add_scalars(step, values)
        telemetry.record_scalars(step, values)

    def flush(self):
        """Push both sinks' buffered tails to disk (the JSONL file is
        line-buffered, the event writer flushes per frame — this covers
        the residue plus any OS-level buffering before a crash)."""
        with self._lock:
            if self._file is not None:
                self._file.flush()
            if self._events is not None:
                self._events.flush()

    def close(self):
        from distributed_tensorflow_tpu.utils import telemetry

        # run teardown is the last guaranteed flush point: drain the
        # span sink too (the final checkpoint's ckpt_write span lands
        # after the last display-cadence flush)
        telemetry.get_tracer().flush()
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None
            if self._events is not None:
                self._events.close()
                self._events = None


class StreamingHistogram:
    """Streaming quantile estimator over geometric buckets (p50/p90/p99).

    The serving path needs latency QUANTILES, not means — a p99 cannot be
    recovered from scalar averages after the fact — but must not hold
    every observation (heavy traffic = millions of samples). Values land
    in geometrically-spaced buckets (``growth`` relative width per
    bucket, so the quantile error is bounded by the bucket ratio, ~4%
    at the default), quantiles read the bucket CDF with log-linear
    interpolation inside the landing bucket. O(1) record, O(buckets)
    quantile, fixed memory. Thread-safe: server handler threads record
    while the metrics cadence reads.

    ``summary(prefix)`` returns the p50/p90/p99/mean/count dict shaped
    for ``MetricsLogger.scalars`` — serving latency lands in the same
    JSONL + TensorBoard event sinks as the training scalars.
    """

    QUANTILES = (0.5, 0.9, 0.99)

    def __init__(self, low: float = 1e-3, high: float = 1e7,
                 growth: float = 1.08):
        if not (0 < low < high) or growth <= 1.0:
            raise ValueError(f"need 0 < low < high and growth > 1, got "
                             f"low={low}, high={high}, growth={growth}")
        self._low = float(low)
        self._log_growth = math.log(growth)
        n = int(math.ceil(math.log(high / low) / self._log_growth))
        # bucket i spans [low*g^i, low*g^(i+1)); +2 for underflow/overflow
        self._counts = [0] * (n + 2)
        self._lock = threading.Lock()
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf

    def _bucket(self, value: float) -> int:
        if value < self._low:
            return 0
        i = int(math.log(value / self._low) / self._log_growth) + 1
        return min(i, len(self._counts) - 1)

    def record(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self._counts[self._bucket(value)] += 1
            self._count += 1
            self._sum += value
            self._min = min(self._min, value)
            self._max = max(self._max, value)

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def mean(self) -> float:
        with self._lock:
            return self._sum / self._count if self._count else 0.0

    def _edge(self, i: int) -> float:
        """Lower edge of bucket ``i`` (i >= 1; bucket 0 is underflow)."""
        return self._low * math.exp((i - 1) * self._log_growth)

    def _snapshot(self) -> tuple:
        """One-lock consistent copy of the full estimator state — the
        quantiles, mean and count a reader derives from it can never
        disagree with each other (a cadence read racing ``record`` used
        to take the lock per quantile and read ``_count`` outside it)."""
        with self._lock:
            return (list(self._counts), self._count, self._sum,
                    self._min, self._max)

    def _quantile_from(self, counts, count, mn, mx, q: float) -> float:
        if not count:
            return 0.0
        rank = q * count
        seen = 0.0
        for i, c in enumerate(counts):
            if not c:
                continue
            if seen + c >= rank:
                if i == 0:
                    return mn
                frac = min(max((rank - seen) / c, 0.0), 1.0)
                lo = self._edge(i)
                val = lo * math.exp(frac * self._log_growth)
                return min(max(val, mn), mx)
            seen += c
        return mx

    def quantile(self, q: float) -> float:
        """Value at quantile ``q`` in [0, 1]; 0.0 when empty. Clamped to
        the observed min/max so sparse histograms don't over-report the
        bucket width."""
        counts, count, _total, mn, mx = self._snapshot()
        return self._quantile_from(counts, count, mn, mx, q)

    def summary(self, prefix: str = "") -> dict:
        """{prefix}p50/p90/p99/mean/count — the scalars dict the serving
        metrics cadence hands to MetricsLogger/events. Computed from ONE
        locked snapshot: the count always agrees with the quantiles even
        while handler threads record concurrently."""
        counts, count, total, mn, mx = self._snapshot()
        out = {f"{prefix}p{int(q * 100)}":
               self._quantile_from(counts, count, mn, mx, q)
               for q in self.QUANTILES}
        out[f"{prefix}mean"] = total / count if count else 0.0
        out[f"{prefix}count"] = float(count)
        return out

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * len(self._counts)
            self._count = 0
            self._sum = 0.0
            self._min = math.inf
            self._max = -math.inf
