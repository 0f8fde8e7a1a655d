"""Tokens of every pod's batch over the whole window, summed over the chips."""


def read(run: dict):
    return run["work"] / run["window_s"]
