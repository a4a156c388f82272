"""Mean `engine.readback` span: from a decode wave's tokens ready to the
end of its step, where the engine reads each slot's token and position
back to the host and retires finished requests."""
from chipbench.metrics._spans import closed_in_window


def read(run):
    rb = closed_in_window(run, "engine.readback")
    return 1e3 * sum(r.end - r.start for r in rb) / len(rb) if rb else None
