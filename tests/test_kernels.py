"""Pallas kernel layer (siddhi_tpu/kernels/): bit-identity + gating.

Every kernel is pinned bit-identical to the XLA formulation it
replaces (on CPU the kernels run under ``interpret=True`` — semantics
-exact, which is what makes these differentials meaningful without a
TPU).  The planner gates are exercised both ways: eligible queries
must actually lower to the kernel (asserted via ``lowered_to``), and
every ineligible/unavailable case must fall back gracefully with a
counted ``kernelFallbackReason`` — never an error, never silently.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.exceptions import SiddhiAppCreationError
from siddhi_tpu.query_api import AttrType

DEFINE = "define stream S (k long, u double, v double); "

# capture-free chain: the class the packed-plane NFA kernel covers
ELIGIBLE = ("@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
            "within 3 sec select b.v as bv insert into Alerts;")

# b's filter captures a.v -> needs the register file -> NFA fallback
CAPTURING = ("@info(name='q') from every a=S[v > 8.0] -> b=S[v > a.v] "
             "within 3 sec select a.v as av, b.v as bv "
             "insert into Alerts;")


def gen_stream(seed, n=60):
    rng = np.random.default_rng(seed)
    ts = 1000 + np.cumsum(rng.integers(1, 400, size=n))
    ks = rng.integers(0, 3, size=n)
    us = rng.uniform(0.0, 20.0, size=n).round(1)
    vs = rng.uniform(0.0, 20.0, size=n).round(1)
    return [([int(k), float(u), float(v)], int(t))
            for k, u, v, t in zip(ks, us, vs, ts)]


def run_app(header, app, sends):
    """-> (rows, lowered_to, statistics_manager)."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(header + DEFINE + app)
        got = []
        rt.add_callback("Alerts", lambda evs: got.extend(e.data for e in evs))
        rt.start()
        h = rt.get_input_handler("S")
        for row, ts in sends:
            h.send(row, timestamp=ts)
        qr = next(iter(rt.query_runtimes.values()), None)
        lowered = getattr(qr, "lowered_to", None)
        sm = rt.app_context.statistics_manager
        rt.shutdown()
        return got, lowered, sm
    finally:
        m.shutdown()


TPU = "@app:playback @app:execution('tpu', instances='16') "


def test_kernels_are_interpreted_off_tpu():
    from siddhi_tpu.kernels import probe

    assert probe.interpret_mode()  # tests are CPU-only by contract


class TestBankSegmentedReduce:
    @pytest.mark.parametrize("op", ["sum", "min", "max"])
    def test_matches_numpy_reference_int32(self, op):
        from siddhi_tpu.kernels import bank_scatter

        rng = np.random.default_rng(11)
        n, r = 700, 256
        rows = rng.integers(0, 40, n).astype(np.int32)
        vals = rng.integers(-1000, 1000, n).astype(np.int32)
        ident = {"sum": 0, "min": np.iinfo(np.int32).max,
                 "max": np.iinfo(np.int32).min}[op]
        # n is not a whole number of event blocks: the kernel pads
        got = np.asarray(bank_scatter.segmented_reduce(
            rows, vals, r, op, ident, interpret=True))
        want = np.full(r, ident, dtype=np.int32)
        getattr(np, {"sum": "add", "min": "minimum", "max": "maximum"}[op]
                ).at(want, rows, vals)
        assert np.array_equal(got, want)

    def test_collision_stress_all_events_one_key(self):
        """The scatter's worst case — every event on ONE row — must
        reduce to the same row values through the kernel and the XLA
        scatter banks (integer-valued f32 sums stay order-free)."""
        from siddhi_tpu.aggregation.runtime import BaseField
        from siddhi_tpu.aggregation.device_bank import DeviceBucketBank

        fields = [
            BaseField("_SUM0", "sum", None, AttrType.LONG),
            BaseField("_MIN1", "min", None, AttrType.LONG),
            BaseField("_MAX2", "max", None, AttrType.LONG),
            BaseField("_SUM3", "sum", None, AttrType.DOUBLE),
        ]
        rng = np.random.default_rng(13)
        n = 2048
        fvals = {
            # sums ride the 16-bit hi/lo split: keep 2048 summands small
            # enough that the int32 hi lane cannot overflow
            "_SUM0": rng.integers(-(2**20), 2**20, n),
            "_MIN1": rng.integers(-(2**60), 2**60, n),
            "_MAX2": rng.integers(-(2**60), 2**60, n),
            # integer-valued floats: f32 sum reassociation cannot bite
            "_SUM3": rng.integers(0, 100, n).astype(np.float64),
        }
        out = {}
        for use_kernel in (False, True):
            bank = DeviceBucketBank(fields, cap=8, use_kernel=use_kernel)
            assert bank.assign([(0, ())])
            # ALL n events collide on the single assigned row
            rows = np.full(n, bank.rows[(0, ())], dtype=np.int32)
            bank.scatter(rows, fvals)
            out[use_kernel] = bank.flush()[(0, ())]
        assert out[False] == out[True], out
        assert out[True]["_SUM0"] == int(fvals["_SUM0"].sum())
        assert out[True]["_MIN1"] == int(fvals["_MIN1"].min())
        assert out[True]["_MAX2"] == int(fvals["_MAX2"].max())
        assert out[True]["_SUM3"] == float(fvals["_SUM3"].sum())


class TestLongExtremaDeviceBank:
    """LONG min/max ride the bank as lexicographic hi/lo int32 pairs —
    the signed 64-bit compare must be exact at full width, kernel and
    XLA scatter alike."""

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_unit_differential_negative_heavy(self, use_kernel):
        from siddhi_tpu.aggregation.runtime import BaseField
        from siddhi_tpu.aggregation.device_bank import DeviceBucketBank

        fields = [BaseField("_MIN0", "min", None, AttrType.LONG),
                  BaseField("_MAX1", "max", None, AttrType.LONG)]
        bank = DeviceBucketBank(fields, cap=16, use_kernel=use_kernel)
        rng = np.random.default_rng(17)
        keys = [(0, ("a",)), (0, ("b",)), (1, ("a",))]
        assert bank.assign(keys)
        ref = {k: [None, None] for k in keys}
        for _batch in range(3):
            n = 200
            ks = rng.integers(0, len(keys), n)
            # negative-heavy incl. values whose hi word ties but lo
            # differs (the lexicographic second pass must decide)
            v = rng.integers(-(2**62), 2**20, n)
            v[::7] = -(2**62) + rng.integers(0, 3, len(v[::7]))
            rows = np.asarray([bank.rows[keys[k]] for k in ks],
                              dtype=np.int32)
            bank.scatter(rows, {"_MIN0": v, "_MAX1": v.copy()})
            for k, x in zip(ks, v):
                cur = ref[keys[k]]
                cur[0] = int(x) if cur[0] is None else min(cur[0], int(x))
                cur[1] = int(x) if cur[1] is None else max(cur[1], int(x))
        got = bank.flush()
        for k in keys:
            assert got[k]["_MIN0"] == ref[k][0], (k, got[k], ref[k])
            assert got[k]["_MAX1"] == ref[k][1], (k, got[k], ref[k])

    AGG_APP = (
        "{mode}@app:playback "
        "define stream S (sym string, v long, ts long); "
        "define aggregation A from S select sym, min(v) as lo, "
        "max(v) as hi group by sym aggregate by ts every sec...min;"
    )
    BASE = 1_600_000_000_000

    def _run_agg(self, mode, vals, probe_bank=False):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(self.AGG_APP.format(mode=mode))
            rt.start()
            agg = rt.aggregations["A"]
            rng = np.random.default_rng(11)
            n = len(vals)
            ts = np.sort(self.BASE + rng.integers(0, 5_000, n)).astype(
                np.int64)
            h = rt.get_input_handler("S")
            for j in range(n):
                h.send([f"s{int(rng.integers(0, 6))}", int(vals[j]),
                        int(ts[j])])
            if probe_bank:
                assert agg._bank is not None, "LONG extrema did not bank"
                assert agg._bank.scatters > 0
                # extrema pairs are excluded from the sum-overflow guard
                assert not agg._bank.long_names
            out = rt.query(
                f"from A within {self.BASE - 1000}, "
                f"{self.BASE + 100_000} per 'seconds' select sym, lo, hi;")
            rt.shutdown()
            return sorted([list(e.data) for e in out], key=lambda r: r[0])
        finally:
            m.shutdown()

    def test_app_level_exact_vs_host(self):
        rng = np.random.default_rng(3)
        vals = rng.integers(-(2**40), 2**40, 300)
        host = self._run_agg("", vals)
        dev = self._run_agg("@app:execution('tpu') ", vals,
                            probe_bank=True)
        assert len(host) == len(dev) > 0
        assert host == dev, (host[:3], dev[:3])

    def test_app_level_kernel_bank_negative_heavy(self):
        rng = np.random.default_rng(5)
        vals = rng.integers(-(2**62), -1, 300)
        host = self._run_agg("", vals)
        kern = self._run_agg("@app:execution('tpu') @app:kernels('bank') ",
                             vals, probe_bank=True)
        assert len(host) == len(kern) > 0
        assert host == kern, (host[:3], kern[:3])


class TestDenseKernelApp:
    def test_eligible_query_lowers_and_matches_xla(self):
        sends = gen_stream(seed=1, n=40)
        plain, lp, _ = run_app(TPU, ELIGIBLE, sends)
        kern, lk, sm = run_app(TPU + "@app:kernels ", ELIGIBLE, sends)
        assert lp == "dense" and lk == "kernel"
        assert kern == plain  # bit-identical, not approximately
        assert not sm.kernel_fallbacks

    def test_capturing_query_falls_back_counted(self):
        sends = gen_stream(seed=2, n=30)
        plain, lp, _ = run_app(TPU, CAPTURING, sends)
        kern, lk, sm = run_app(
            TPU + "@app:kernels @app:statistics('basic') ",
            CAPTURING, sends)
        assert lk == "dense"  # graceful: query still runs on XLA
        assert kern == plain
        assert sm.kernel_fallbacks.get("q") == 1
        assert "register file" in sm.kernel_fallback_reasons["q"]
        stats = sm.stats()
        assert any(k.endswith("q.kernelFallbacks") for k in stats)

    def test_compiler_refusal_is_a_counted_fallback_with_its_message(
            self, monkeypatch):
        """Enablement compiles the kernel; a refusal is known at app
        creation, with the compiler's message in the fallback reason."""
        from siddhi_tpu.kernels import dense_step

        def refuse(engine):
            raise RuntimeError("Mosaic failed to compile TPU kernel: nope")

        monkeypatch.setattr(dense_step, "smoke_compile", refuse)
        sends = gen_stream(seed=1, n=20)
        plain, _, _ = run_app(TPU, ELIGIBLE, sends)
        kern, lowered, sm = run_app(TPU + "@app:kernels ", ELIGIBLE, sends)
        assert lowered == "dense" and kern == plain
        assert sm.kernel_fallbacks.get("q") == 1
        assert "Mosaic failed to compile TPU kernel: nope" in (
            sm.kernel_fallback_reasons["q"])

    def test_no_annotation_means_no_kernel_machinery(self):
        sends = gen_stream(seed=3, n=30)
        _rows, lowered, sm = run_app(TPU, ELIGIBLE, sends)
        assert lowered == "dense"
        assert not sm.kernel_fallbacks


class TestScanKernelApp:
    def test_hotkey_scan_kernel_bit_identity(self):
        """Skewed keys promoting mid-run: the fused scan-chain kernel
        must emit exactly what the two-pass associative scan emits."""
        app = ("partition with (k of S) begin "
               "@info(name='q') from every a=S[v > 8.0] -> b=S[v > 12.0] "
               "select b.v as bv insert into Alerts; "
               "end;")
        rng = np.random.default_rng(51)
        sends, t = [], 1000
        for i in range(360):
            t += int(rng.integers(1, 60))
            phase = (3 * i) // 360
            hot = phase != 1 and rng.random() < 0.85
            k = 7 if hot else int(rng.integers(0, 30))
            sends.append(([int(k), float(round(rng.uniform(0, 20), 1)),
                           float(round(rng.uniform(0, 20), 1))], int(t)))

        def run(kern):
            m = SiddhiManager()
            try:
                rt = m.create_siddhi_app_runtime(
                    TPU + "@app:hotkeys(k='4', promote='0.3', demote='0.1') "
                    + ("@app:kernels('scan') " if kern else "")
                    + DEFINE + app)
                got = []
                rt.add_callback(
                    "Alerts", lambda evs: got.extend(e.data for e in evs))
                rt.start()
                h = rt.get_input_handler("S")
                for row, ts in sends:
                    h.send(row, timestamp=ts)
                lowered, hot_m = None, {}
                for pr in rt.partitions.values():
                    for qr in pr.dense_query_runtimes.values():
                        lowered = qr.lowered_to
                        hot_m = qr.pattern_processor.hot_metrics()
                rt.shutdown()
                return got, lowered, hot_m
            finally:
                m.shutdown()

        kern, lk, hot = run(True)
        plain, lp, _ = run(False)
        assert lp == "hotkey" and lk == "hotkey+kernel"
        assert hot["hotkeyPromotions"] >= 1, hot  # the scan actually ran
        assert kern == plain


class TestKernelsAnnotation:
    def test_requires_tpu_mode(self):
        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError,
                               match="needs @app:execution"):
                m.create_siddhi_app_runtime(
                    "@app:kernels " + DEFINE + ELIGIBLE)
        finally:
            m.shutdown()

    def test_unknown_kind_rejected(self):
        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError,
                               match="unknown kernel kind"):
                m.create_siddhi_app_runtime(
                    "@app:execution('tpu') @app:kernels('nfa,warp') "
                    + DEFINE + ELIGIBLE)
        finally:
            m.shutdown()

    def test_false_keeps_kernels_off(self):
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(
                TPU + "@app:kernels('false') " + DEFINE + ELIGIBLE)
            rt.start()
            assert rt.app_context.kernels is False
            qr = next(iter(rt.query_runtimes.values()))
            assert qr.lowered_to == "dense"
            rt.shutdown()
        finally:
            m.shutdown()

    def test_kind_subset_skips_other_kinds_silently(self):
        # bank-only request: the pattern query is NOT a fallback — nfa
        # was never asked for
        sends = gen_stream(seed=4, n=20)
        _rows, lowered, sm = run_app(
            TPU + "@app:kernels('bank') ", ELIGIBLE, sends)
        assert lowered == "dense"
        assert not sm.kernel_fallbacks
