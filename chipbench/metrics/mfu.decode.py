"""Model FLOPs of the decoded tokens in the window (2 x active
parameters, plus attention over each token's context), over the window's
seconds x the chip's peak, in %."""
from chipbench import work
from chipbench.metrics._common import in_window


def read(run):
    if run.peak is None:
        return None
    flops = sum(work.decode_token_flops(run.config, len(r.prompt) + j)
                for r in run.requests for j, t in enumerate(r.tokens)
                if j >= 1 and in_window(run, t))
    return 100.0 * flops / (run.window_s * run.peak["bf16_flops"])
