"""Set-up seconds: process start to the first timed call, compilation,
weights, data and the checked first steps included."""


def read(run: dict):
    return run["setup_s"]
