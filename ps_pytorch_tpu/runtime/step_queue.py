"""The bookkeeping of a step loop that keeps one step queued on the device.

Such a loop has one rule: nothing between two dispatches waits for the
device, so the host's work (loader, put, dispatch, bookkeeping) hides under
the device's. What follows from the rule is the same in every trainer and
lives here once; ``Trainer.train`` and ``LMTrainer.train`` call it with what
differs between them (which of a step's scalars a record holds, which the
watchdogs see, and how one record is built and written):

- the loop's one wait, ``device_sync``, reads the PREVIOUS step's scalars
  with this step already queued (``sync``), so the watchdogs trail by a step;
- a logged step's record is written once the NEXT step is queued, from
  metrics that wait has already seen finished (``log_through``), under the
  spans ``metrics_sync`` and ``log_write``;
- what drains the device anyway writes the waiting records first: a
  checkpoint, the loop's last step, a halt, an exception on its way out
  (``log_on_the_way_out``);
- ``ahead`` says whether the mechanism engaged: was the chip still busy with
  the previous step when this one was queued.
"""

import time
from collections import deque
from typing import Callable, Dict, Optional, Sequence

import jax


def read_scalars(m: dict, keys: Optional[Sequence[str]] = None
                 ) -> Dict[str, float]:
    """The scalars of a step's metrics ``m`` as floats, those named in
    ``keys`` (all of them for None; a name ``m`` lacks is left out). ONE
    read: every scalar's copy to the host is started before any is waited
    for (a ``float()`` each is a round trip each, 0.2-0.8 ms on the chip's
    host). A scalar read before costs nothing again."""
    picked = m if keys is None else {k: m[k] for k in keys if k in m}
    return {k: float(v) for k, v in jax.device_get(picked).items()}


class QueuedSteps:
    """One loop's queued-step state: the metrics of the step before, the
    logged steps whose record is not written yet, and when records' scalars
    were last read.

    ``write(step, scalars, step_time=..., **fields)`` builds and writes one
    record: ``scalars`` are the step's own, named in ``record``; ``fields``
    what ``log_later`` was given for that step. ``watch`` names the scalars
    ``sync`` hands to the watchdogs. None means every scalar the step
    returns."""

    def __init__(self, tracer, start_step: int, write: Callable[..., None],
                 *, record: Optional[Sequence[str]] = None,
                 watch: Optional[Sequence[str]] = None):
        self._tracer, self._write = tracer, write
        self._record, self._watch = record, watch
        self._m_prev: Optional[dict] = None
        self._unwritten: deque = deque()
        # When records' scalars were last read, and up to which step: the
        # records read in one go share the wall time since, per step since.
        # With a step queued the reads follow the device's pace, not the
        # host's, and the step_times of a run add up to its wall time.
        self._t_read, self._read_step = time.monotonic(), start_step

    def ahead(self) -> int:
        """At the close of ``host_dispatch``: was the chip still busy with
        the previous step when this one was queued? (A query, not a wait.)"""
        m = self._m_prev
        return int(m is not None and not m["loss"].is_ready())

    def log_later(self, step: int, m: dict, **fields) -> None:
        """``step`` is a logged step: its record waits until ``log_through``
        reaches it."""
        self._unwritten.append((step, m, fields))

    def sync(self, m: dict) -> Dict[str, float]:
        """The loop's one wait for the device: the watched scalars of the
        step BEFORE the one that returned ``m``, read with that one already
        queued ({} on the loop's first step). So the wall time between two
        calls is a true per-step duration, and the watchdogs and the previous
        step's record get their values at no further sync."""
        prev: Dict[str, float] = {}
        with self._tracer.span("device_sync"):
            if self._m_prev is not None:
                prev = read_scalars(self._m_prev, self._watch)
            self._m_prev = m        # frees the scalars just read
        return prev

    def last_watched(self) -> Dict[str, float]:
        """The watched scalars of the last step dispatched, for the check
        after the loop: ``sync`` trails by one step, and a NaN on the final
        step must still trip ({} if no step ran)."""
        if self._m_prev is None:
            return {}
        return read_scalars(self._m_prev, self._watch)

    def log_through(self, upto: int) -> None:
        """Writes the waiting records of steps up to ``upto``, their scalars
        read in one go. For the step before the one just queued the read
        waits for nothing (``sync`` saw it finished); for the queued step
        itself it drains the device."""
        due = []
        while self._unwritten and self._unwritten[0][0] <= upto:
            due.append(self._unwritten.popleft())
        if not due:
            return
        with self._tracer.span("metrics_sync"):
            scalars = [read_scalars(m, self._record) for _, m, _ in due]
        now = time.monotonic()
        last = due[-1][0]
        step_time = (now - self._t_read) / (last - self._read_step)
        self._t_read, self._read_step = now, last
        with self._tracer.span("log_write"):
            for (step, _, fields), own in zip(due, scalars):
                self._write(step, own, step_time=step_time, **fields)

    def restart_clock(self, step: int) -> None:
        """After work that is no step's (a checkpoint): the next record's
        ``step_time`` counts from now."""
        self._t_read, self._read_step = time.monotonic(), step

    def log_on_the_way_out(self) -> None:
        """In the loop's ``except``: every waiting record, best effort. A
        crashed or interrupted run keeps the log of every step it dispatched,
        and a failure here must not mask the real error."""
        try:
            if self._unwritten:
                self.log_through(self._unwritten[-1][0])
        except Exception as err:
            print(f"LOG a waiting step record was not written: {err!r}")
