"""Metrics: JSONL sink + rate tracking.

Replaces ``tf.summary`` FileWriter event files and StepCounterHook's
steps/sec (SURVEY.md §5.5) with a JSONL stream (one object per record —
trivially greppable and the format ``bench.py`` consumes) plus
examples/sec/chip computation per the driver metric (BASELINE.json:2).
"""

from __future__ import annotations

import json
import numbers
import os
import time
from typing import Any, TextIO

import jax


class MetricsLogger:
    """Append-only JSONL metrics writer; process 0 writes, like the chief's
    summary thread (supervisor.py:675-679 parity). With ``tb_logdir`` the
    same records also stream to a TensorBoard event file
    (utils/tb_events.py — the tf.summary FileWriter role, SURVEY.md §5.5):
    every numeric field of a record that carries a ``step`` becomes a
    scalar, one-level-nested dicts flatten to ``outer/inner`` tags."""

    def __init__(self, path: str | None = None, *, also_stdout: bool = False,
                 tb_logdir: str | None = None):
        self.path = path
        self.also_stdout = also_stdout
        self._f: TextIO | None = None
        self._tb = None
        if jax.process_index() == 0:
            if path:
                os.makedirs(os.path.dirname(os.path.abspath(path)),
                            exist_ok=True)
                self._f = open(path, "a", buffering=1)
            if tb_logdir:
                from .tb_events import EventFileWriter
                self._tb = EventFileWriter(tb_logdir)

    @staticmethod
    def _flatten_scalars(record: dict[str, Any]) -> dict[str, float]:
        def num(v):
            # numbers.Number covers numpy scalars too — the JSONL sink
            # accepts them via default=float, so the TB sink must as well
            return isinstance(v, numbers.Number)

        out: dict[str, float] = {}
        if "histogram" in record:
            # distribution records go to TB as HistogramProtos via
            # log_histogram; their JSONL summary stats are not scalars
            return out
        for k, v in record.items():
            if k in ("step", "time"):
                continue
            if isinstance(v, dict):
                for k2, v2 in v.items():
                    if num(v2):
                        out[f"{k}/{k2}"] = float(v2)
            elif num(v):
                out[k] = float(v)
        return out

    def log(self, record: dict[str, Any]) -> None:
        record = dict(record, time=time.time())
        line = json.dumps(record, default=float)
        if self._f is not None:
            self._f.write(line + "\n")
        if self._tb is not None and "step" in record:
            scalars = self._flatten_scalars(record)
            if scalars:
                self._tb.scalars(int(record["step"]), scalars,
                                 wall_time=record["time"])
        if self.also_stdout and jax.process_index() == 0:
            print(line, flush=True)

    def log_histogram(self, step: int, tag: str, values) -> None:
        """Distribution record: the JSONL gets compact summary stats
        (greppable), the TB sink gets the full HistogramProto
        (tf.summary.histogram parity)."""
        import numpy as np
        v = np.asarray(values, np.float64).reshape(-1)
        if v.size == 0:
            return
        fin = v[np.isfinite(v)]
        stats = ({"min": float(fin.min()), "max": float(fin.max()),
                  "mean": float(fin.mean()), "std": float(fin.std())}
                 if fin.size else {})
        self.log({"step": step, "histogram": tag, **stats,
                  "count": int(v.size),
                  # NaN would be invalid strict JSON; surface the
                  # pathology as a count instead
                  "nonfinite": int(v.size - fin.size)})
        if self._tb is not None:
            self._tb.histogram(step, tag, v)

    def flush(self) -> None:
        """Push buffered JSONL bytes to disk (the serving drain path
        flushes before the process exits on SIGTERM)."""
        if self._f is not None:
            self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None
        if self._tb is not None:
            self._tb.close()
            self._tb = None


class RateTracker:
    """steps/sec and examples/sec/chip over a sliding window
    (StepCounterHook parity, basic_session_run_hooks.py:674)."""

    def __init__(self, batch_size: int = 0, num_chips: int | None = None):
        self.batch_size = batch_size
        self.num_chips = num_chips or jax.device_count()
        self._t0: float | None = None
        self._s0 = 0

    def start(self, step: int) -> None:
        self._t0 = time.perf_counter()
        self._s0 = step

    def rates(self, step: int) -> dict[str, float]:
        """Rates since the last start(); restarts the window."""
        now = time.perf_counter()
        if self._t0 is None or step <= self._s0:
            self.start(step)
            return {}
        dt = now - self._t0
        steps = step - self._s0
        out = {
            "steps_per_sec": steps / dt,
            "sec_per_step": dt / steps,
        }
        if self.batch_size:
            out["examples_per_sec"] = steps * self.batch_size / dt
            out["examples_per_sec_per_chip"] = (
                out["examples_per_sec"] / self.num_chips)
        self.start(step)
        return out
