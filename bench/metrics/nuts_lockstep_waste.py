"""Share of the leapfrog steps that the vectorized chains ran in lockstep
but did not need: 1 - (steps each chain's tree took) / (chains x the
deepest chain's steps at each draw), from the telemetry ``num_steps``
counter over warmup and sampling."""
import numpy as np


def read(run):
    useful = padded = 0
    for fit in run.fits:
        for steps in getattr(fit, "steps", {}).values():
            steps = np.asarray(steps)                  # (chains, draws)
            useful += int(steps.sum())
            padded += steps.shape[0] * int(steps.max(axis=0).sum())
    return 100.0 * (1.0 - useful / padded) if padded else None
