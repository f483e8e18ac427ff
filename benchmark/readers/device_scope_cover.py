"""How much of the device's busy time in the window ran under any of the
program's name scopes, in per cent: self time of the scoped operations
over the union of the window's device operations.  What is left is
operations the program gave no scope (a loop's own time between its
body's operations among them).  Nothing without a trace, without one
scoped operation in it, or where the device was never busy."""

from ..harness import device_scopes


def read(params: dict, run: dict):
    scopes = device_scopes.scopes_of(run)
    if scopes is None or not scopes["busy_ns"]:
        return None
    return 100.0 * device_scopes.scoped_ns(scopes) / scopes["busy_ns"]
