"""``fraud_pass``'s traffic beside a deployment that checkpoints itself.

The batches are ``fraud_pass.traffic``'s: batch ``n`` at seed ``s`` is
``fraud16_1m.saturated``'s batch ``n`` at seed ``s``.  The checkpoints
are the deployment's own (its ``@app:persist`` daemon); nothing here
calls ``persist()``.  What this file adds is what a run needs round
them: the store's directory, resolved under the machine's temporary
directory and emptied before the app is built, so that no revision of
an earlier run can be taken for this run's; and ``recover``, which the
reference calls after the clock has stopped: the newest committed
revision restored into a second runtime and sent the batches that
followed its capture; and the place of the window among the
checkpoints.  The daemon ticks at a fixed rate from ``start()``, so
where its ticks fall in the window is the set-up's length, which
differs from run to run (a cold compile, a slow import), and a window
whose end cuts a checkpoint's stall counts another share of stalls than
one whose end does not.  So the last warm-up batch is made only when the
next checkpoint has been committed (``await_commit_s``): the first
checkpoint is warm-up like the first batch, its wait is set-up, and a
window of whole periods of the daemon (three in the cell's 30 s) ends,
as it began, just behind a commit, clear of any stall.  Nothing here
calls ``persist()`` or touches the daemon: the wait only watches the
store's directory for the manifest a commit renames into place.
"""

from __future__ import annotations

import atexit
import glob
import os
import shutil
import tempfile
import threading
import time

import fraud_pass
import numpy as np

from siddhi_tpu.core.stream import StreamCallback
# the function the planner builds an app's store through (PR 48): a
# commit that lacks it cannot run this cell, and ends here
from siddhi_tpu.durability.store import open_store


class CheckpointedPasses(fraud_pass.PassSchedule):
    """``PassSchedule`` and the way back from a revision."""

    def __init__(self, config, size, limit_s, *traffic):
        super().__init__(config["stream"], *traffic)
        self.config = config
        self.size = size
        self.location = size["location"]
        self.app_name = config["name"]
        self.limit_s = limit_s
        self.made_ms = int(time.time() * 1000)
        self.restore_s = None   # what restore_last_revision() took
        # the longest the last warm-up batch waits for the daemon's next
        # commit; 0 (no wait) unless ``make`` sets the configuration's
        self.await_commit_s = 0

    def batch(self, n: int):
        if n == -1 and self.await_commit_s:
            limit_s, self.await_commit_s = self.await_commit_s, 0
            self._await_commit(limit_s)
        return super().batch(n)

    def _newest_committed(self) -> str:
        """The newest revision whose manifest is in place (a revision's
        name begins with the millisecond of its capture)."""
        return max((os.path.basename(os.path.dirname(p)) for p in glob.glob(
            os.path.join(self.location, self.app_name, "*.ckpt",
                         "MANIFEST.json"))), default="")

    def _await_commit(self, limit_s: float) -> None:
        """Return when the daemon has committed a revision newer than
        the newest there is now, or after ``limit_s`` (said, and the run
        goes on: the reference counts the revisions)."""
        seen, t0 = self._newest_committed(), time.perf_counter()
        while (newest := self._newest_committed()) == seen:
            if time.perf_counter() - t0 > limit_s:
                print(f"warm-up: no revision committed in {limit_s} s; the "
                      "window starts wherever the daemon's ticks fall",
                      flush=True)
                return
            time.sleep(0.02)
        print(f"warm-up: waited {time.perf_counter() - t0:.3f} s for the "
              f"daemon's next commit ({newest}); the window starts behind it",
              flush=True)

    def last_of_replay(self, n_c: int) -> int:
        """The last batch a replay from ``n_c`` sends: the end of the
        pass after ``n_c``'s.  State that crosses the capture matters to
        the end of its own pass and must be gone in the next."""
        p = (n_c + self.warmup) // self.per_pass
        return (p + 2) * self.per_pass - self.warmup - 1

    def recover(self, location: str, before=None):
        """``(n_c, rows)``: the newest committed revision under
        ``location`` restored into a fresh runtime of the same app (the
        daemon off), ``n_c`` the batch its clock names, ``rows`` what the
        app delivered for the batches from ``n_c + 1`` to
        ``last_of_replay(n_c)`` as columns ``ts``, ``v1``, ``v16``.
        ``None`` where no revision restores, or where the recovery
        passed ``limit_s``.  The committed revisions are removed when it
        is done.  ``before(store)`` runs first (a test plants its fault
        there)."""
        out = []

        def work():
            try:
                out.append(self._recover(location, before))
            except Exception as e:   # no revision restores: not correct
                print(f"recover: {e!r}", flush=True)
                out.append(None)

        t = threading.Thread(target=work, name="bench-recover", daemon=True)
        t.start()
        t.join(self.limit_s)
        if t.is_alive():
            print(f"recover: still running after {self.limit_s} s; given up",
                  flush=True)
            return None
        return out[0]

    def _recover(self, location, before):
        from siddhi_tpu import SiddhiManager

        store = open_store(location, 2)
        if before is not None:
            before(store)
        manager = SiddhiManager()
        rt = manager.create_siddhi_app_runtime(
            self.config["recover_header"].format(
                **{**self.size, "location": location}) + " "
            + self.config["app"])
        callback = _Rows()
        got = callback.got
        rt.add_callback(self.config["output"], callback)
        rt.start()
        try:
            t0 = time.perf_counter()
            revision = rt.restore_last_revision()
            self.restore_s = time.perf_counter() - t0
            if revision is None:
                print("recover: the store holds no committed revision",
                      flush=True)
                return None
            n_c = int(self.batch_of(rt.applied_time()))
            last = self.last_of_replay(n_c)
            send = rt.get_input_handler(self.config["stream"]).send_batch
            t0 = time.perf_counter()
            for n in range(n_c + 1, last + 1):
                send(self.batch(n))
            rt.drain_device_emits()
            print(f"recover: revision {revision} restored in "
                  f"{self.restore_s:.3f} s (restore_last_revision() alone); "
                  f"its clock names batch {n_c}; batches {n_c + 1}..{last} "
                  f"replayed in {time.perf_counter() - t0:.3f} s, "
                  f"{sum(len(b.timestamps) for b in got)} rows", flush=True)
        finally:
            rt.shutdown()
            manager.shutdown()
            store.clear_all_revisions(self.app_name)
        rows = {"ts": np.concatenate([b.timestamps for b in got]
                                     or [np.zeros(0, np.int64)])}
        for name in ("v1", "v16"):
            rows[name] = np.concatenate(
                [np.asarray(b.columns[name], dtype=np.float64) for b in got]
                or [np.zeros(0)])
        return n_c, rows


class _Rows(StreamCallback):
    """The recovering runtime's callback: keeps the batches."""

    def __init__(self):
        self.got = []

    def receive_batch(self, batch):
        self.got.append(batch)


def make(seed: int, config: dict, traffic_spec: dict, rehearsal: bool):
    which = "rehearsal" if rehearsal else "full"
    size = config[which]
    # the harness formats the header with this same block: what is
    # resolved here is what the deployment's annotation names
    size["location"] = size["location"].replace(
        "$TMPDIR", tempfile.gettempdir()).replace("$PID", str(os.getpid()))
    shutil.rmtree(size["location"], ignore_errors=True)
    # what a checkpoint in flight at the end leaves, and a killed run's
    atexit.register(shutil.rmtree, size["location"], True)
    schedule = CheckpointedPasses(
        config, size, config["recover_limit_s"][which],
        *fraud_pass.traffic(np.random.default_rng(seed), size["partitions"],
                            traffic_spec[which]["batch"],
                            traffic_spec["batches_per_pass"]))
    schedule.await_commit_s = config["await_commit_s"][which]
    return schedule
