"""EMA-validation early stopping (paper §4 'Model learning' and §5.4).

Training stops when the CURRENT validation loss exceeds the exponential
moving average of past validation losses; the average resets whenever new
samples arrive. Lower ``weight`` = more aggressive stopping (Fig. 5a)."""
from __future__ import annotations


class EMAEarlyStop:
    def __init__(self, weight: float = 0.9, enabled: bool = True):
        assert 0.0 < weight < 1.0
        self.weight = weight
        self.enabled = enabled
        self.reset()

    def reset(self):
        self.ema = None
        self.stopped = False

    def update(self, val_loss: float) -> bool:
        """Feed one epoch's validation loss; returns stopped flag."""
        if self.ema is None:
            self.ema = val_loss
            return False
        if self.enabled and val_loss > self.ema:
            self.stopped = True
        # the EMA in increment form: it stays between val_loss and the old
        # EMA after rounding, so a plateau (val_loss == ema) cannot drift
        # the EMA below it and fake an increase on the next epoch
        self.ema = self.ema + (1 - self.weight) * (val_loss - self.ema)
        return self.stopped
