"""The part of the traced window in which no operation ran on the device,
per unit of work and scaled: host milliseconds per remap."""

from ..harness.paths import lookup


def read(params: dict, run: dict):
    tr = run.get("trace")
    per = lookup(run, params["per"])
    if not tr or not per:
        return None
    return params.get("scale", 1) * (tr["window_s"] - tr["busy_s"]) / per
