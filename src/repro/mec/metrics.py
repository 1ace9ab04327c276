"""Running metrics matching the paper's §VI-D definitions.

* SSP — #successful tasks / #total tasks.
* Average inference accuracy — Σ accuracy of *successful* tasks / #total.
* Average throughput — #successful tasks / total elapsed time (tasks/s).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class RunningMetrics:
    total_tasks: int = 0
    successful: int = 0
    accuracy_sum: float = 0.0
    reward_sum: float = 0.0
    slots: int = 0
    slot_s: float = 30e-3

    def update(self, result, active=None, *, read=np.asarray) -> None:
        """Fold one slot in. ``read`` converts each device array to the
        host (four reads, or three without ``active``); the serving
        engine passes its spanned, counted reader."""
        success = read(result.success)
        acc = read(result.accuracy)
        if active is None:
            active = np.ones_like(success, dtype=bool)
        else:
            active = read(active) > 0.5
        self.total_tasks += int(active.sum())
        self.successful += int((success & active).sum())
        self.accuracy_sum += float((acc * (success & active)).sum())
        self.reward_sum += float(read(result.reward))
        self.slots += 1

    @property
    def ssp(self) -> float:
        return self.successful / max(self.total_tasks, 1)

    @property
    def avg_accuracy(self) -> float:
        return self.accuracy_sum / max(self.total_tasks, 1)

    @property
    def throughput(self) -> float:
        return self.successful / max(self.slots * self.slot_s, 1e-9)

    @property
    def avg_reward(self) -> float:
        return self.reward_sum / max(self.slots, 1)

    def summary(self) -> dict:
        return {
            "ssp": self.ssp,
            "avg_accuracy": self.avg_accuracy,
            "throughput_tps": self.throughput,
            "avg_reward": self.avg_reward,
            "tasks": self.total_tasks,
        }
