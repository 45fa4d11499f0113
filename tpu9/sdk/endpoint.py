"""@endpoint / @asgi / @realtime decorators.

Reference analogue: ``sdk/src/beta9/abstractions/endpoint.py:43``
(Endpoint/ASGI/RealtimeASGI). Usage:

    from tpu9 import endpoint

    @endpoint(cpu=1, memory="16Gi", tpu="v5e-1", keep_warm_seconds=30)
    def predict(prompt: str = ""):
        return {"output": model(prompt)}

    predict.deploy("my-model")
"""

from __future__ import annotations

from .base import RunnerAbstraction


class Endpoint(RunnerAbstraction):
    stub_type = "endpoint"


class ASGI(RunnerAbstraction):
    stub_type = "asgi"


class RealtimeASGI(RunnerAbstraction):
    stub_type = "realtime"


def _decorator(cls):
    def wrap(func=None, **kwargs):
        if func is not None and callable(func) and not kwargs:
            return cls(func)
        def inner(f):
            return cls(f, **kwargs)
        return inner
    return wrap


endpoint = _decorator(Endpoint)
asgi = _decorator(ASGI)
realtime = _decorator(RealtimeASGI)
