"""Mean decode wave: the call to its tokens on the host."""
from chipbench.metrics._common import waves


def read(run):
    w = waves(run)
    return 1e3 * sum(b - a for a, b, _ in w) / len(w) if w else None
