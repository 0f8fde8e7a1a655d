"""Mean milliseconds per round that the host waits for the round program's
loss to come back: the ``engine/wait`` spans of a ``repro.obs.Recorder``
attached to the engine in the traced run."""


def read(run: dict):
    spans = [t1 - t0 for name, t0, t1 in run["host_spans"] if name == "engine/wait"]
    return sum(spans) / len(spans) * 1e3 if spans else None
