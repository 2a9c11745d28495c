"""Step-time watchdog: flags a step (a train step, a serving tick) that
takes more than `factor` times the running EWMA of the steps before it."""
from __future__ import annotations

from collections import deque


class StepWatchdog:
    """EWMA step-time tracker: flags straggler steps (the detection signal a
    cluster scheduler needs for mitigation at real scale). `stragglers`
    keeps the last 1024 flags as (step, dt, ewma), so a process that
    never stops holds a bounded record."""

    def __init__(self, factor: float = 2.0, alpha: float = 0.1):
        self.ewma = None
        self.factor = factor
        self.alpha = alpha
        self.stragglers: deque = deque(maxlen=1024)

    def observe(self, step: int, dt: float):
        if self.ewma is None:
            self.ewma = dt
            return False
        slow = dt > self.factor * self.ewma
        if slow:
            self.stragglers.append((step, dt, self.ewma))
            # clamp the baseline update for flagged steps: folding the
            # straggler sample itself into the EWMA drags the baseline
            # toward the pathology, so a run of consecutive stragglers
            # raises its own detection threshold until it stops firing.
            # The baseline may still drift up (a real regime change - e.g.
            # a longer sequence bucket - should eventually be accepted),
            # but never by more than the flagging threshold per step.
            dt = self.factor * self.ewma
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return slow
