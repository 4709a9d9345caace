"""Preemption-safe signal handling.

A TPU fleet's steady state includes SIGTERM: spot/preemptible
reclamation, cluster drains, supervisor kills. Today that loses the run;
with :class:`PreemptionGuard` it costs at most one step — the handler
only sets a flag (async-signal-safe: no jax, no I/O), and the engine
honors it at the next step *boundary* with a normal verified checkpoint,
then exits with a recognizable code so the supervisor relaunches instead
of declaring success.

The guard chains any previously-installed handler: a framework above us
(notebook, launcher) keeps seeing its signals.
"""

import signal
import threading
from typing import Optional, Sequence

from deepspeed_tpu.utils.logging import logger

# conventional "terminated by SIGTERM" exit code (128 + 15): the elastic
# agent must NOT read a preempt-save exit as job-finished (rc=0) or a
# 5%-done run would be reported complete
DEFAULT_PREEMPT_EXIT_CODE = 143


class PreemptionGuard:
    """Convert termination signals into a step-boundary checkpoint request.

    Usage (the engine wires this via ``engine.enable_preemption_checkpoint``
    or the ``resilience.preempt_save_dir`` config key)::

        guard = PreemptionGuard().install()
        ...
        if guard.requested:          # checked at each step boundary
            sig = guard.consume()
            engine.save_checkpoint(dir)

    Signal handlers only work in the main thread; elsewhere ``install``
    logs and degrades to a manually-triggered flag (``request()``).

    A SECOND SIGINT while a request is already pending escalates: the
    previous handlers are restored and ``KeyboardInterrupt`` is raised
    immediately — pressing Ctrl-C twice always gets you out of a process
    stuck off the step boundary (stuck compile, hung collective).
    """

    def __init__(self, signals: Sequence[str] = ("SIGTERM", "SIGINT")):
        self.signal_names = [s if isinstance(s, str) else signal.Signals(s).name
                             for s in signals]
        self._requested: Optional[str] = None
        self._previous = {}
        self.installed = False

    # -- handler ---------------------------------------------------------
    def _on_signal(self, signum, frame):
        # flag-only: a handler that touches jax / files / locks can deadlock
        # a process that was mid-dispatch when the signal landed
        if self._requested is not None and signum == signal.SIGINT:
            # escalation escape hatch: a SECOND Ctrl-C while a request is
            # already pending means the step boundary never came (stuck
            # compile, hung collective) — restore the previous handlers and
            # interrupt NOW rather than swallowing Ctrl-C forever
            self.uninstall()
            raise KeyboardInterrupt
        self._requested = signal.Signals(signum).name
        prev = self._previous.get(signum)
        # chain only genuinely-custom handlers (a framework above us keeps
        # seeing its signals). NOT default_int_handler: it raises
        # KeyboardInterrupt right here, aborting mid-step — the exact lost
        # run the flag-then-boundary contract exists to prevent.
        if callable(prev) and prev is not signal.default_int_handler:
            prev(signum, frame)

    def install(self) -> "PreemptionGuard":
        if threading.current_thread() is not threading.main_thread():
            logger.warning("PreemptionGuard: not on the main thread, signal handlers "
                           "cannot be installed; preemption checkpoints will only fire "
                           "via an explicit request()")
            return self
        for name in self.signal_names:
            sig = getattr(signal, name)
            self._previous[sig] = signal.getsignal(sig)
            signal.signal(sig, self._on_signal)
        self.installed = True
        return self

    def uninstall(self) -> None:
        for sig, prev in self._previous.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, TypeError):
                pass
        self._previous = {}
        self.installed = False

    # -- flag ------------------------------------------------------------
    @property
    def requested(self) -> bool:
        return self._requested is not None

    def request(self, name: str = "manual") -> None:
        """Programmatic trigger (tests; cooperative shutdown paths)."""
        self._requested = name

    def consume(self) -> Optional[str]:
        """Return-and-clear the pending request (the signal name)."""
        name, self._requested = self._requested, None
        return name
