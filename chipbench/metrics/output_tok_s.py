"""Every token that reached the host in the window, over its seconds."""
from chipbench.metrics._common import in_window


def read(run):
    n = sum(1 for r in run.requests for t in r.tokens if in_window(run, t))
    return n / run.window_s
