"""Milliseconds per protocol round: the whole window over the rounds
completed in it."""


def read(run: dict):
    return run["window_s"] / len(run["calls"]) * 1e3
