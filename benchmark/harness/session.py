"""One run of one cell: the clock, the measured window, what was compared.

A driver gets a Session, does its set-up, opens the window, drives the
system, closes the window, runs its correctness pass and returns.  The
session owns everything a driver must not decide for itself: when set-up
ends, the profiler, the count of programs that were new inside the
window, the device's peak memory, and the list of numbers that decide
``correct``.
"""

import faulthandler
import sys
import time

from . import trace

# jax.monitoring: fired once for every program lowered in this process,
# whether its executable then comes from the persistent cache or not
LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Session:
    def __init__(self, t_start: float, seed: int, seconds: float,
                 traced: bool, cell: dict, config: dict, mix: dict,
                 device: dict, trace_dir: str, guard: bool = False):
        self.t_start = t_start
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.cell, self.config, self.mix = cell, config, mix
        self.device, self.trace_dir = device, trace_dir
        self.guard = guard      # end a run that hangs; never a test's process
        self.end_to_end: dict = {}      # name -> value, the driver's
        self.facts: dict = {}           # what the per-layer readers read
        self.compared: dict = {}        # name -> value, limit, relation
        self.attempted = self.failed = 0
        self.lowered = 0
        self._window = None
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if event == LOWERED_EVENT:
            self.lowered += 1

    # -- the measured window -------------------------------------------------

    def open_window(self) -> float:
        """Set-up ends here.  Returns the window's start on
        time.monotonic(); the profiler, when on, is already running."""
        if self.traced:
            trace.start(self.trace_dir)
        if self.guard:
            # set-up may compile for minutes; from here a run that hangs
            # ends with its stacks inside the 360 s a run may take
            faulthandler.dump_traceback_later(
                self.seconds + 270, exit=True, file=sys.__stderr__)
        self._window = trace.span("window")
        self._window.__enter__()
        self._lowered0 = self.lowered
        self._cpu0 = time.process_time()
        self.t0 = time.monotonic()
        self.end_to_end["setup_s"] = self.t0 - self.t_start
        self.facts["setup_s"] = self.end_to_end["setup_s"]
        return self.t0

    def close_window(self) -> float:
        """Returns the window's length in seconds on the host's clock."""
        self.t1 = time.monotonic()
        self.facts["window_cpu_s"] = time.process_time() - self._cpu0
        self._window.__exit__(None, None, None)
        if self.traced:
            trace.stop()
        self.facts["window_s"] = self.t1 - self.t0
        self.compare("programs_new_in_window",
                     self.lowered - self._lowered0, 0)
        return self.t1 - self.t0

    def read_memory_peak(self) -> None:
        """The peak on the fullest chip; read before the reference runs,
        because a process's peak never falls again."""
        import jax
        self.device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices())

    # -- what decides `correct` ----------------------------------------------

    def compare(self, name: str, value, limit, relation: str = "<=") -> None:
        """One number that decides ``correct``, beside its limit.
        ``relation`` is how a sound value stands to the limit."""
        if relation not in ("<=", ">="):
            raise ValueError(relation)
        self.compared[name] = {"value": value, "limit": limit,
                               "relation": relation}

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            c["value"] is not None and
            (c["value"] <= c["limit"] if c["relation"] == "<="
             else c["value"] >= c["limit"])
            for c in self.compared.values())
