"""95th percentile of every gap between consecutive tokens of one
request, over the gaps that end in the window (one pooled tail)."""
from chipbench.metrics._common import in_window, percentile_ms


def read(run):
    gaps = [b - a for r in run.requests
            for a, b in zip(r.tokens, r.tokens[1:]) if in_window(run, b)]
    return percentile_ms(gaps, 95)
