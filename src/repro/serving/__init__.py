"""Serving runtime: the continuous-batching engine (`engine`), the cache
operations it needs to admit a request into a slot (`kvcache`), and the
host spans it times itself with (`spans`)."""
from repro.serving.engine import Engine, Request
