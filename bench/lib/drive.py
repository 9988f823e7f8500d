"""The measured window: whole jobs back to back.

The job drives the program through its entry points and is timed on the
host clock.  A host span (``jax.profiler.TraceAnnotation``) marks each job,
so a traced run can put device time under it.
"""

from __future__ import annotations

import time
from typing import Callable, List


class Units:
    """Runs ``unit()`` back to back from the window's start until
    ``seconds`` have passed; the unit in flight then is finished and
    counted.  ``time_per_unit`` is the whole window over the units done."""

    def __init__(self, unit: Callable[[int], object], span: str):
        self.unit, self.span = unit, span
        self.done: List[float] = []
        self.last = None

    def run(self, t0: float, seconds: float) -> None:
        import jax

        i = 0
        while True:
            with jax.profiler.TraceAnnotation(self.span):
                self.last = self.unit(i)
            self.done.append(time.perf_counter())
            i += 1
            if self.done[-1] - t0 >= seconds:
                return

    def time_per_unit(self, t0: float) -> float:
        return (self.done[-1] - t0) / len(self.done)
