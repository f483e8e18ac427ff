"""Dotted paths into what a run knows, so that a metric's file can say
which number it reads: "facts.dispatches", "config.profile.k"."""


def lookup(run: dict, path: str):
    """The value at ``path``, or None where any step is missing."""
    at = run
    for step in path.split("."):
        if not isinstance(at, dict) or step not in at:
            return None
        at = at[step]
    return at
