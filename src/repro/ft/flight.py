"""Crash flight recorder (survey §8.1/§8.2, MegaScale-style) — a bounded
ring buffer of structured per-step events dumped to JSON for post-mortem
attribution.

At cluster scale the expensive half of a failure is rarely the restart — it
is the hours spent reconstructing *which* rank/step broke and what the run
did about it. The flight recorder is the always-on answer: every component
of the fault-tolerance stack logs into one bounded ring
(:class:`FlightRecorder`), and the ring is dumped to a parseable JSON file
the moment something goes wrong:

- :class:`repro.ft.anomaly.Monitor` logs a ``"step"`` event per recorded
  step (loss, grad-norm, wall-time) and an ``"anomaly"`` event per
  detection (statistical or externally noted);
- :func:`repro.ft.recovery.run_with_recovery` logs ``"policy"`` decisions
  (anomaly kind → action), ``"restore"`` events (which tier served it:
  memory / memory-rebuild / disk), ``"fault"`` events for every injected
  fault that fired (:mod:`repro.ft.inject`), and ``"preempt"`` events;
- :class:`repro.checkpoint.store.CheckpointManager` and
  :class:`repro.checkpoint.memory.MemoryCheckpointTier` log checkpoint/tier
  events (saves, persist failures, GC evictions, verify-before-evict skips),
  each save with the seconds of its parts (``checksum_seconds``, and for
  the RAM tier ``copy_seconds``/``mirror_seconds``; ``mem.restore`` with
  ``fetch_seconds``/``put_seconds``);
- the loop writes one ``"loop"`` event per step whose ``seconds`` maps each
  of its sections (``train.fetch``, ``train.inject``, ``train.step``,
  ``train.readback``, ``train.monitor``, ``train.straggler``,
  ``train.ckpt``, ``train.mem_ckpt``, ``train.restore``) to the host
  seconds it took, and one ``"setup"`` event for the step-0 save (or the
  resume's restore): the place to look for where a step's host time went.

Those sections and the tiers' parts are :class:`span` s: one
``jax.profiler.TraceAnnotation`` each, so a profiler trace shows them on the
device trace's clock over the ops they cover, timed once on
``time.perf_counter``. Every duration here is on that clock; only the dump's
``wall_time`` is a wall timestamp.

The ring is bounded (``maxlen``, knob ``RecoveryPolicy.flight_len``) so a
month-long run carries a constant-size black box. ``dump()`` writes
atomically (tmp + ``os.replace``) and sanitizes values, so it is safe to
call from an exception handler mid-crash; the dump path is carried on
:class:`repro.ft.recovery.RunReport` (and on ``RecoveryExhausted``) so the
autopsy artifact is one attribute away from the failure it describes.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from pathlib import Path
from typing import Any, Dict, Optional

import jax


def _jsonable(v: Any) -> Any:
    """Best-effort JSON sanitization — a crash dump must never crash."""
    if v is None or isinstance(v, (bool, int, str)):
        return v
    if isinstance(v, float):
        # json rejects nothing here (nan/inf serialize as tokens some
        # parsers refuse) — stringify non-finite floats for portability
        return v if v == v and v not in (float("inf"), float("-inf")) \
            else repr(v)
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    try:
        return float(v)          # numpy / jax scalars
    except (TypeError, ValueError):
        return repr(v)


class span:
    """One section of the trainer, on the profiler's clock and the host's.

    ``with span(name, step) as sp:`` opens
    ``jax.profiler.TraceAnnotation(name, step=step)``, so a traced run shows
    the section over the device ops it covers, and times it once with
    ``time.perf_counter``: ``sp.seconds`` after the block, also added to
    ``into[name]`` when ``into`` is given. With a
    :class:`repro.ft.straggler.StragglerTimer` and one of its ``section``
    names, the armed ``slow`` fault of that section sleeps inside the span
    and the detector is fed the span's own seconds. With the profiler off
    the annotation records nothing, and a span costs a few microseconds of
    host time.
    """

    __slots__ = ("name", "step", "into", "straggler", "section", "rank",
                 "seconds", "_ann", "_t0")

    def __init__(self, name: str, step: int,
                 into: Optional[Dict[str, float]] = None, straggler=None,
                 section: Optional[str] = None, rank: Optional[int] = None):
        self.name, self.step, self.into = name, int(step), into
        self.straggler, self.section, self.rank = straggler, section, rank
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name, step=self.step)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        try:
            if self.straggler is not None:
                self.straggler.slow_sleep(self.section, self.step, self.rank)
            self.seconds = time.perf_counter() - self._t0
        finally:
            self._ann.__exit__(*exc)
        if self.into is not None:
            self.into[self.name] = self.into.get(self.name, 0.0) + self.seconds
        if self.straggler is not None:
            self.straggler.observe(self.section, self.step, self.seconds,
                                   self.rank)
        return False


class FlightRecorder:
    """Bounded ring of structured events + atomic JSON dump.

    ``record(kind, step, **data)`` appends one event (cheap: a dict into a
    deque; safe from the checkpoint persist thread — deque appends are
    atomic under the GIL). ``dump(reason=...)`` writes the whole ring plus
    run-level context to ``path`` (constructor default, overridable per
    call) and returns the path written.
    """

    def __init__(self, maxlen: int = 256, path: Optional[str] = None):
        self.maxlen = int(maxlen)
        self.path = str(path) if path is not None else None
        self.events: deque = deque(maxlen=self.maxlen)
        self.dumped_path: Optional[str] = None
        self._t0 = time.perf_counter()

    def record(self, kind: str, step: int, **data: Any) -> None:
        self.events.append({"t": time.perf_counter() - self._t0, "kind": kind,
                            "step": int(step), **data})

    def dump(self, reason: str, path: Optional[str] = None,
             extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Atomically write the ring to JSON; returns the path (None when no
        path is configured anywhere). Never raises — a failing black-box
        write must not mask the crash being recorded."""
        out = path or self.path
        if out is None:
            return None
        payload = {
            "reason": reason,
            "wall_time": time.time(),
            "run_seconds": time.perf_counter() - self._t0,
            "n_events": len(self.events),
            "maxlen": self.maxlen,
            "extra": _jsonable(extra or {}),
            "events": [_jsonable(e) for e in self.events],
        }
        try:
            out_p = Path(out)
            out_p.parent.mkdir(parents=True, exist_ok=True)
            tmp = out_p.with_name(out_p.name + ".tmp")
            tmp.write_text(json.dumps(payload, indent=1))
            os.replace(tmp, out_p)
        except OSError:
            return self.dumped_path
        self.dumped_path = str(out)
        return self.dumped_path
