"""Device-to-host reads per decode wave: the `reads` of every
`engine.step` span over the number of `engine.wave` spans."""
from chipbench.metrics._spans import closed_in_window


def read(run):
    waves = closed_in_window(run, "engine.wave")
    if not waves:
        return None
    steps = closed_in_window(run, "engine.step")
    return sum(s.attrs["reads"] for s in steps) / len(waves)
