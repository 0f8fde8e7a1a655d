"""Share of the traced window in which the device ran no operation,
averaged over the chips, in percent: the reader of every
``idle_share.<kind>`` metric."""


def read(run: dict):
    tr = run["trace"]
    if not tr or tr["window_s"] <= 0 or not tr["devices"]:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
