"""Device self time of the operations that were traced under some of the
program's name scopes, inside the window, per unit of work and scaled:
milliseconds of the chip per remap in the resolve chain's stage A.  An
operation counts where its path (harness/device_scopes.py) holds any
scope of ``under`` and none of ``not_under``, once however many of them
it holds; a `while` is charged for none of its body.  0 where the
program carries scopes and none of these ran; nothing without a trace,
without one scoped operation in it (a program from before the scopes),
or without the unit."""

from ..harness import device_scopes
from ..harness.paths import lookup


def read(params: dict, run: dict):
    scopes = device_scopes.scopes_of(run)
    per = lookup(run, params["per"])
    if scopes is None or not per:
        return None
    return (params.get("scale", 1) * device_scopes.under_ns(
        scopes, params["under"], params.get("not_under", ())) / per)
