"""Grouped-matmul kernel: least time of its calls (from the tokens
served) over its device time in the trace, in %; backlog cells."""
from chipbench.metrics._common import gmm_roofline_pct as read  # noqa: F401
