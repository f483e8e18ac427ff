"""The device's idle share of the traced window, in per cent: 1 - the
union of the intervals in which an operation ran on the device, over the
window.  Nothing without a trace."""


def read(params: dict, run: dict):
    tr = run.get("trace")
    if not tr or not tr["planes"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
