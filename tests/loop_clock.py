"""Clocks to inject into an ``IngestStage`` (its ``clock`` argument):
what the stage's rule observes, decided by the test and not by the host
the test runs on.  Shared by tests/test_ingest_pipeline.py (a bare
stage) and tests/test_device_pipeline.py (the five runtimes)."""


class LoopClock:
    """A closed loop on one device: the sender is back ``think_s`` after
    a hand-back, the host prepares a batch for ``host_s``, the device
    takes one step at a time; an inline gate keeps the host for what is
    left of its step, a deferred one for what the next batch's
    preparation did not cover."""

    def __init__(self):
        self.t = 100.0
        self.done = 0.0     # when the device has finished what it holds

    def __call__(self):
        return self.t

    def arrives(self, arrive, think_s, host_s):
        """The sender comes back, the stage clocks the arrival
        (``arrive``: its ``IngestStage.arrive``), the host prepares."""
        self.t += think_s
        arrive()
        self.t += host_s

    def dispatched(self, step_s) -> float:
        """A step of ``step_s`` goes out; returns when it is done."""
        self.done = max(self.t, self.done) + step_s
        return self.done

    def resolved(self, done_at) -> float:
        """The host gets to a gate whose step is done at ``done_at``;
        returns the seconds it kept the host."""
        blocked = max(0.0, done_at - self.t)
        self.t += blocked
        return blocked


class PacedClock:
    """A sender that is a second away: every reading is a second after
    the last, so each batch arrives a second after the stage handed the
    last one back, as a paced source's does.  The rule is asked at every
    arrival and never opens the window, whatever the gates took."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1.0
        return self.t
