"""Mean milliseconds per round of the program's argument upload and enqueue:
the ``engine/dispatch`` spans of a ``repro.obs.Recorder`` attached to the
engine in the traced run."""


def read(run: dict):
    spans = [t1 - t0 for name, t0, t1 in run["host_spans"] if name == "engine/dispatch"]
    return sum(spans) / len(spans) * 1e3 if spans else None
