"""Transactional checkpointing: atomic commit, incremental, reshard."""
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, CheckpointManager
from repro.core import Cluster, NotFound


@pytest.fixture()
def cluster(tmp_path):
    c = Cluster(n_servers=4, data_dir=str(tmp_path), replication=1,
                region_size=256 * 1024)
    yield c
    c.close()


@pytest.fixture()
def fs(cluster):
    return cluster.client()


def tree_of(step):
    rng = np.random.default_rng(step)
    return {
        "params": {
            "embed": rng.standard_normal((64, 32)).astype(np.float32),
            "layers": {"w1": rng.standard_normal((32, 128)).astype(np.float32),
                       "b1": np.zeros(128, dtype=np.float32)},
        },
        "opt": {"mu": rng.standard_normal((64, 32)).astype(np.float32),
                "count": np.int32(step)},
    }


def trees_equal(a, b):
    np.testing.assert_array_equal(a["params"]["embed"], b["params"]["embed"])
    np.testing.assert_array_equal(a["params"]["layers"]["w1"],
                                  b["params"]["layers"]["w1"])
    np.testing.assert_array_equal(a["opt"]["mu"], b["opt"]["mu"])
    assert int(a["opt"]["count"]) == int(b["opt"]["count"])


def test_save_restore_roundtrip(fs):
    mgr = CheckpointManager(fs)
    t = tree_of(1)
    mgr.save(1, t)
    got = mgr.restore(t)
    trees_equal(t, got)
    assert mgr.latest_step() == 1


def test_latest_flips_atomically(fs):
    mgr = CheckpointManager(fs)
    mgr.save(1, tree_of(1))
    mgr.save(2, tree_of(2))
    assert mgr.latest_step() == 2
    got = mgr.restore(tree_of(0))          # template only provides structure
    trees_equal(tree_of(2), got)
    # older checkpoint remains addressable
    got1 = mgr.restore(tree_of(0), step=1)
    trees_equal(tree_of(1), got1)


def test_reader_never_sees_partial_checkpoint(cluster, fs):
    """Kill the writer mid-save: latest still points at the old manifest."""
    mgr = CheckpointManager(fs)
    mgr.save(1, tree_of(1))

    class Boom(Exception):
        pass

    t2 = tree_of(2)
    # sabotage: fail after some data files are written but before commit
    orig_commit = mgr._commit
    def failing_commit(*a, **k):
        raise Boom()
    mgr._commit = failing_commit
    with pytest.raises(Boom):
        mgr.save(2, t2)
    mgr._commit = orig_commit

    reader = CheckpointManager(cluster.client())
    assert reader.latest_step() == 1
    trees_equal(tree_of(1), reader.restore(tree_of(0)))


def test_incremental_save_shares_unchanged_leaves(cluster, fs):
    mgr = CheckpointManager(fs)
    t1 = tree_of(1)
    mgr.save(1, t1)
    # step 2: only opt.count changes
    t2 = {"params": t1["params"],
          "opt": {"mu": t1["opt"]["mu"], "count": np.int32(2)}}
    writes_before = sum(s.stats.bytes_written
                        for s in cluster.servers.values())
    stats = mgr.save(2, t2, prev_step=1)
    writes_after = sum(s.stats.bytes_written
                       for s in cluster.servers.values())
    assert stats["leaves_shared"] == 4      # embed, w1, b1, mu
    assert stats["bytes_written"] == 4      # just the int32 count
    # physical writes ≈ dirents + manifest, far below the 41 KB of params
    assert writes_after - writes_before < 4000
    trees_equal(t2, mgr.restore(tree_of(0)))


def test_multihost_sharded_save(fs):
    mgr = CheckpointManager(fs)
    big = {"w": np.arange(100_000, dtype=np.float32)}   # 400 KB → sharded
    for host in range(4):
        mgr.save(5, big, host_id=host, num_hosts=4)
    got = mgr.restore({"w": None})
    np.testing.assert_array_equal(got["w"], big["w"])
    man = mgr.read_manifest(5)
    assert man["leaves"]["w"]["shards"] == 4


def test_zero_copy_reshard(cluster, fs):
    mgr = CheckpointManager(fs)
    big = {"w": np.arange(50_000, dtype=np.float32),
           "small": np.float32(3.0)}
    cursor = {"epoch": 1, "step_in_epoch": 7}
    for host in range(2):
        mgr.save(1, big, host_id=host, num_hosts=2,
                 extra={"pipeline": cursor})
    writes_before = sum(s.stats.bytes_written
                        for s in cluster.servers.values())
    mgr.reshard(1, new_shards=4, dst_step=2)
    writes_after = sum(s.stats.bytes_written
                       for s in cluster.servers.values())
    # resharding 200 KB of data writes only manifest+dirent metadata
    assert writes_after - writes_before < 8000
    got = mgr.restore({"w": None, "small": None}, step=2)
    np.testing.assert_array_equal(got["w"], big["w"])
    man = mgr.read_manifest(2)
    assert man["leaves"]["w"]["shards"] == 4
    # the resharded manifest describes the same training state
    assert man["pipeline"] == cursor and man["resharded_from"] == 1


def test_retention_unlinks_old_steps(fs):
    mgr = CheckpointManager(fs, keep=2)
    for step in (1, 2, 3, 4):
        mgr.save(step, {"x": np.full(10, step, np.float32)})
    assert mgr.list_steps() == [3, 4]
    with pytest.raises(NotFound):
        mgr.restore({"x": None}, step=1)


def test_async_checkpointer(fs):
    mgr = CheckpointManager(fs)
    ck = AsyncCheckpointer(mgr)
    t = tree_of(7)
    ck.save(7, t)
    # trainer mutates its arrays immediately — snapshot must protect us
    t["params"]["embed"][:] = -1
    ck.wait()
    got = mgr.restore(tree_of(0))
    assert not np.allclose(got["params"]["embed"], -1)
    assert mgr.latest_step() == 7


def test_restore_missing_raises(fs):
    mgr = CheckpointManager(fs)
    with pytest.raises(NotFound):
        mgr.restore({"x": None})
