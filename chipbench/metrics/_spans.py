"""The program's own host spans (`repro.serving.spans`) that closed in the
run's window. A program without that module has none: a reader then finds
nothing and returns None."""
from chipbench.metrics._common import in_window


def closed_in_window(run, name: str) -> list:
    try:
        from repro.serving import spans
    except ImportError:
        return []
    return [r for r in spans.records()
            if r.name == name and in_window(run, r.end)]
