"""What several metric readers share. A reader gets the run: its window
(t_open, t_close, window_s on the host clock), its requests (arrival,
prompt, prefill span, the host time of every token), its decode waves and
prefills (start, end, live slots or prompt length), setup_s, the
configuration, the chip's peaks and, with --trace 1, the trace's
reduction (`trace_reduce.reduce`)."""
import numpy as np

from chipbench import work


def in_window(run, t) -> bool:
    return run.t_open < t <= run.t_close


def due(run):
    """Requests due in the window: scheduled to arrive before its close."""
    return [r for r in run.requests if run.t_open <= r.arrival < run.t_close]


def first_token_wait(run, r) -> float:
    """Arrival to first token on the host; one still waiting at the close
    counts with the time it has waited so far."""
    t = r.tokens[0] if r.tokens and r.tokens[0] <= run.t_close else run.t_close
    return t - r.arrival


def percentile_ms(values, q):
    return float(np.percentile(np.asarray(values, float), q) * 1e3) \
        if len(values) else None


def waves(run):
    return [w for w in run.waves if w[0] >= run.t_open]


def prefills(run):
    return [p for p in run.prefills if p[0] >= run.t_open]


def gmm_roofline_pct(run):
    """Least time of every grouped-matmul call in the window, from the
    tokens served, over the kernel's device time in the trace."""
    if not run.trace or run.peak is None:
        return None
    kernel_s = run.trace["kernel_s"].get("moe_gmm", 0.0)
    if kernel_s <= 0:
        return None
    c, peak = run.config, run.peak
    layers = c["num_hidden_layers"]
    least = sum(work.gmm_least_seconds(c, n, peak) for _, _, n in waves(run))
    least += sum(work.gmm_least_seconds(c, n, peak) for _, _, n in prefills(run))
    return 100.0 * layers * least / kernel_s


def idle_pct(run):
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
