"""Mesh sharding tests on the 8-virtual-device CPU mesh (conftest.py).

Validates the scale-out surface (SURVEY.md §2.3 mapping): partition-axis
sharding via shard_map, shard-local state with per-shard scratch rows,
host-side event routing with collision-round splitting, and the psum'd
global match count.
"""

import numpy as np
import pytest

APP = (
    "define stream Txn (key long, v double); "
    "@info(name='f') from every a=Txn[v > 100.0] -> b=Txn[v > a.v]<3:5> "
    "within 10 min "
    "select a.v as base, b[0].v as b0 insert into Alerts;"
)


@pytest.fixture(scope="module")
def sharded():
    from siddhi_tpu.ops.dense_nfa import compile_pattern
    from siddhi_tpu.parallel import ShardedPatternEngine, make_mesh

    mesh = make_mesh(8)
    eng = compile_pattern(APP, "f", n_partitions=8 * 64)
    return ShardedPatternEngine(eng, mesh)


class TestRouting:
    def test_route_to_shards_layout(self):
        from siddhi_tpu.parallel import route_to_shards

        part = np.asarray([0, 64, 65, 130, 3])
        cols = {"v": np.asarray([1.0, 2.0, 3.0, 4.0, 5.0], dtype=np.float32)}
        ts = np.asarray([10, 20, 30, 40, 50])
        lp, rc, rts, valid, pos = route_to_shards(4, 64, part, cols, ts)
        B = len(lp) // 4
        assert B >= 16  # pow-2 padded with a floor, bounding recompiles
        # shard 0 got partitions 0 and 3 (local ids 0, 3)
        assert sorted(lp[:B][valid[:B]].tolist()) == [0, 3]
        # shard 1 got 64, 65 -> local 0, 1
        assert sorted(lp[B:2 * B][valid[B:2 * B]].tolist()) == [0, 1]
        # shard 2 got 130 -> local 2
        assert lp[2 * B:3 * B][valid[2 * B:3 * B]].tolist() == [2]
        # values follow their events; pos maps inputs to slots
        assert rc["v"][2 * B:3 * B][valid[2 * B:3 * B]].tolist() == [4.0]
        assert valid.sum() == 5
        for i in range(5):
            assert rc["v"][pos[i]] == cols["v"][i]
        # padded lanes target the per-shard scratch row, never partition 0
        assert (lp[~valid] == 64).all()

    def test_out_of_range_partition_rejected(self):
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError
        from siddhi_tpu.parallel import route_to_shards

        with pytest.raises(SiddhiAppCreationError):
            route_to_shards(2, 8, np.asarray([99]), {}, np.asarray([1]))


class TestShardedEngine:
    def _drive(self, sharded, part, values):
        state = sharded.init_state()
        result = None
        for i, v in enumerate(values):
            n = len(part)
            state, emit, out, total = sharded.process(
                state, np.asarray(part),
                {"v": np.full(n, v, dtype=np.float32),
                 "key": np.zeros(n, dtype=np.float32)},
                np.full(n, 1_000_000 + i * 100, dtype=np.int64),
            )
            result = (state, emit, out, total)
        return result

    def test_match_count_psummed_across_shards(self, sharded):
        state = sharded.init_state()
        part = np.asarray([i * 64 + 1 for i in range(8)])  # one key per shard
        totals = []
        for i, v in enumerate([150.0, 160.0, 170.0, 180.0]):
            state, emit, out, total = sharded.process(
                state, part,
                {"v": np.full(8, v, dtype=np.float32),
                 "key": np.zeros(8, dtype=np.float32)},
                np.full(8, 1_000_000 + i * 100, dtype=np.int64),
            )
            totals.append(total)
        # the 3rd b completes the <3:5> count on every shard at once
        assert totals == [0, 0, 0, 8]
        assert emit.tolist() == list(range(8))
        # per-event outputs mapped back to input order: [a.v, b[0].v]
        assert out[0].tolist() == [150.0, 160.0]

    def test_collision_rounds_same_partition(self, sharded):
        # the whole escalation for ONE key arrives in a single batch;
        # process() must split rounds so state transitions don't race
        state = sharded.init_state()
        part = np.asarray([5, 5, 5, 5])
        state, emit, out, total = sharded.process(
            state, part,
            {"v": np.asarray([150.0, 160.0, 170.0, 180.0], dtype=np.float32),
             "key": np.zeros(4, dtype=np.float32)},
            np.asarray([1_000_000, 1_000_100, 1_000_200, 1_000_300], dtype=np.int64),
        )
        assert total == 1
        assert emit.tolist() == [3]

    def test_epoch_millis_timestamps(self, sharded):
        # absolute epoch-ms int64 timestamps must survive the relative-
        # timestamp normalization (raw int32 truncation would corrupt)
        state = sharded.init_state()
        base = 1_753_000_000_000
        part = np.asarray([9])
        totals = []
        for i, v in enumerate([150.0, 160.0, 170.0, 180.0]):
            state, emit, out, total = sharded.process(
                state, part,
                {"v": np.asarray([v], dtype=np.float32),
                 "key": np.zeros(1, dtype=np.float32)},
                np.asarray([base + i * 100], dtype=np.int64),
            )
            totals.append(total)
        assert totals == [0, 0, 0, 1]

    def test_shard_isolation_and_reset(self, sharded):
        state, emit, out, total = self._drive(
            sharded, [3 * 64 + 7],
            [150.0, 160.0, 170.0, 180.0])
        assert total == 1
        active = sharded.engine.layout.field(state, "active")
        # scratch rows and every partition row are clear after emission
        assert not active.any()

    def test_state_sharding_placement(self, sharded):
        state = sharded.init_state()
        assert len(state["rows"].sharding.device_set) == 8
        assert state["rows"].shape[0] == 8 * 65  # 64 partitions + scratch


class TestMeshNeverBorrowsDevices:
    """A mesh asked for on a platform that is short must fail — never
    land on devices of another platform, never become a host fallback."""

    def test_make_mesh_raises_when_platform_is_short(self):
        import jax

        from siddhi_tpu.core.exceptions import SiddhiAppCreationError
        from siddhi_tpu.parallel import make_mesh

        n = len(jax.devices())
        with pytest.raises(SiddhiAppCreationError, match=f"has {n}"):
            make_mesh(n + 1)
        assert make_mesh(n).devices.size == n

    def test_app_asking_for_too_many_devices_fails_at_creation(self):
        from siddhi_tpu import SiddhiManager
        from siddhi_tpu.core.exceptions import SiddhiAppCreationError

        m = SiddhiManager()
        try:
            with pytest.raises(SiddhiAppCreationError, match="need 16"):
                m.create_siddhi_app_runtime(
                    "@app:execution('tpu', partitions='64', devices='16') "
                    "define stream S (k long, v double); "
                    "partition with (k of S) begin "
                    "from every a=S[v > 1.0] -> b=S[v > a.v] "
                    "select a.v as x, b.v as y insert into Out; end;")
        finally:
            m.shutdown()
