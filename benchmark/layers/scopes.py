"""Device time per batch by the program's ``jax.named_scope``, from the
trace the harness hands every reader (``run.trace``, ``lib/xplane.py``).

Self time: every instant of a device plane's busy time goes to the
operation innermost there (a ``while`` loses what its body's operations
take) and to the innermost ``siddhi.*`` scope on that operation's path
(an operation under ``siddhi.dense.rounds`` > ``.run`` > ``.gather``
counts for ``.gather`` only), so no instant is counted twice and the
scopes of a cell, with ``unscoped``, add up to its
``device_busy_ms_per_batch``: same window, same batches, mean over the
device planes.

A metric names its scope: ``<family>_<phase>_ms_per_batch`` reads
``siddhi.<family>.<phase>`` (``rows.window_slot_ms_per_batch``:
``siddhi.window.slot``); the families of the pattern engine go without
saying (``events.gather_ms_per_batch``: ``siddhi.dense.gather``,
``events.count_psum_ms_per_batch``: ``siddhi.shard.count_psum``);
``unscoped_ms_per_batch`` is the time under no scope.  A scope's time
includes its Pallas kernel's own call, which
``<...>_kernel_ms_per_batch`` reads alone (``events.run_kernel_ms_per_
batch``: the custom call of ``kernels/dense_run.py`` under
``siddhi.dense.run``, without the copies and gathers round it).  A scope
a later deployment adds needs its entry in ``BENCHMARK.json`` and no
edit here.
A scope with no operation in the window yields nothing, and a trace
with no ``siddhi.*`` scope at all (an executable from a compile cache
older than the scopes) yields nothing for any name, ``unscoped``
included: never zero."""

from lib.xplane import KERNEL

SUFFIX = "_ms_per_batch"
IMPLIED = ("dense", "shard")    # families a metric's name may leave out
UNSCOPED = "unscoped"
OF_KERNEL = "_kernel"


def scopes_named(what: str):
    """The scopes the part of a metric's name before ``SUFFIX`` can
    mean, most explicit first."""
    family, _, phase = what.partition("_")
    named = [f"siddhi.{family}.{phase}"] if phase else []
    return named + [f"siddhi.{f}.{what}" for f in IMPLIED]


def read(run):
    trace = getattr(run, "trace", None)
    if trace is None or not trace.batches:
        return {}
    by_key = trace.scope_seconds()
    seconds, kernels = {}, {}    # a scope's time holds its kernel's
    for key, s in by_key.items():
        scope = key.removesuffix(KERNEL) if key else None
        seconds[scope] = seconds.get(scope, 0.0) + s
        if scope != key:
            kernels[scope] = s
    out = {}
    for name in run.wanted if by_key else ():
        what = name.split(".", 1)[-1]
        if not what.endswith(SUFFIX):
            continue
        what, table = what[:-len(SUFFIX)], seconds
        if what.endswith(OF_KERNEL):
            what, table = what[:-len(OF_KERNEL)], kernels
        for scope in [None] if what == UNSCOPED else scopes_named(what):
            if scope in table:
                out[name] = 1e3 * table[scope] / trace.batches
                break
    return out
