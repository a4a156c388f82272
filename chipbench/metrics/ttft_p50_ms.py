"""Median time to first token: scheduled arrival to the token on the
host, over every request due in the window."""
from chipbench.metrics._common import due, first_token_wait, percentile_ms


def read(run):
    return percentile_ms([first_token_wait(run, r) for r in due(run)], 50)
