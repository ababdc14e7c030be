"""Multi-host GSPMD mesh ingestion (docs/mesh.md): shard planning through
the reader's own arithmetic, global-array assembly on the 8-device CPU
simulation, elastic reshard on host loss, and the mesh telemetry surface.
"""
import time

import numpy as np
import pytest

from petastorm_tpu.jax import (MeshDataLoader, MeshHostLostError,
                               MeshReaderFactory)
from petastorm_tpu.reader import _reset_one_shot_warnings, make_batch_reader

pytestmark = pytest.mark.mesh


@pytest.fixture(scope="module")
def scalar_store(tmp_path_factory):
    """Plain Parquet store: 800 rows / 40 row groups of 20 rows."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = tmp_path_factory.mktemp("mesh_scalar")
    n = 800
    pq.write_table(
        pa.table({"id": np.arange(n, dtype=np.int64),
                  "x": (np.arange(n) * 0.5).astype(np.float32)}),
        str(path / "part0.parquet"), row_group_size=20)
    return f"file://{path}"


@pytest.fixture(scope="module")
def token_store(tmp_path_factory):
    """Petastorm token store: 16 NGram windows of 32 tokens, one per
    row group."""
    from dataset_utils import write_token_store
    path = tmp_path_factory.mktemp("mesh_tokens")
    url = f"file://{path}/tokens"
    write_token_store(url, windows=16, window=32)
    return url


def _valid_rows(batch, column="id"):
    arr = np.asarray(batch[column])
    if "__valid__" in batch:
        return arr[np.asarray(batch["__valid__"])].tolist()
    return arr.tolist()


def _epoch_ids(factory, **kwargs):
    kwargs.setdefault("drop_last", False)
    kwargs.setdefault("pad_last", True)
    ids = []
    with MeshDataLoader(factory, **kwargs) as loader:
        for batch in loader:
            ids.extend(_valid_rows(batch))
    return ids


# --------------------------------------------------------------- planning
def test_epoch_plan_is_the_reader_shard_plan(scalar_store):
    """plan[h] must be bit-identical to what a cur_shard=h/shard_count=H
    reader plans (same modulo arithmetic, same seeded pre-shuffle)."""
    factory = MeshReaderFactory(scalar_store, batched=True)
    loader = MeshDataLoader(factory, batch_size=80, num_hosts=4, seed=11)
    plan = loader.epoch_plan(0)
    assert sorted(o for host in plan for o in host) == list(range(40))
    for h in range(4):
        with make_batch_reader(scalar_store, cur_shard=h, shard_count=4,
                               shard_seed=11, shuffle_row_groups=False,
                               workers_count=1) as reader:
            shard_ids = sorted(int(i) for b in reader for i in b.id)
        subset_ids = []
        with factory(plan[h]) as reader:
            for b in reader:
                subset_ids.extend(int(i) for i in b.id)
        assert sorted(subset_ids) == shard_ids
    loader.close()


def test_rowgroup_subset_reader_preserves_order_and_validates(scalar_store):
    with make_batch_reader(scalar_store, shuffle_row_groups=False,
                           workers_count=1,
                           rowgroup_subset=[7, 2, 5]) as reader:
        firsts = [int(b.id[0]) for b in reader]
    assert firsts == [140, 40, 100]
    with pytest.raises(ValueError, match="out of range"):
        make_batch_reader(scalar_store, shuffle_row_groups=False,
                          rowgroup_subset=[999])
    with pytest.raises(ValueError, match="duplicate"):
        make_batch_reader(scalar_store, shuffle_row_groups=False,
                          rowgroup_subset=[1, 1])
    with pytest.raises(ValueError, match="mutually exclusive"):
        make_batch_reader(scalar_store, rowgroup_subset=[1],
                          cur_shard=0, shard_count=2)
    # the order IS the contract: a ventilation shuffle underneath it is
    # rejected, not silently honored (shuffle the ordinal list instead)
    with pytest.raises(ValueError, match="exactly the given"):
        make_batch_reader(scalar_store, shuffle_row_groups=True,
                          rowgroup_subset=[1, 2])


def test_factory_rejects_loader_owned_kwargs(scalar_store):
    with pytest.raises(ValueError, match="owns"):
        MeshReaderFactory(scalar_store, batched=True, cur_shard=0,
                          shard_count=2)


def test_batch_divisibility_and_tail_validation(scalar_store):
    factory = MeshReaderFactory(scalar_store, batched=True)
    with pytest.raises(ValueError, match="divide evenly"):
        MeshDataLoader(factory, batch_size=81)
    with pytest.raises(ValueError, match="ragged tail"):
        MeshDataLoader(factory, batch_size=80, drop_last=False)


# ------------------------------------------------- acceptance e2e: parity
def test_mesh_epoch_multiset_matches_single_host(scalar_store):
    """The acceptance e2e: an 8-simulated-device mesh epoch delivers the
    same global sample multiset as a 1-host run of the same seed/shard
    plan — and every batch is one globally-sharded jax.Array."""
    import jax
    factory = MeshReaderFactory(scalar_store, batched=True)
    shapes = []
    with MeshDataLoader(factory, batch_size=80, seed=3, num_epochs=1,
                        drop_last=False, pad_last=True) as loader:
        mesh_ids = []
        for batch in loader:
            arr = batch["id"]
            assert isinstance(arr, jax.Array)
            assert len(arr.sharding.device_set) == 8
            assert arr.shape[0] == 80
            shapes.append(arr.shape)
            mesh_ids.extend(_valid_rows(batch))
        report = loader.mesh_report()
    single_ids = _epoch_ids(factory, batch_size=80, seed=3, num_epochs=1,
                            num_hosts=1)
    assert sorted(mesh_ids) == sorted(single_ids) == list(range(800))
    assert report["reshard_events"] == 0 and not report["hosts_lost"]
    # every host fed: the per-host rowgroup counters cover the whole plan
    assert sum(h["rowgroups"] for h in report["per_host"].values()) == 40


def test_mesh_epochs_reshuffle_by_seed(scalar_store):
    factory = MeshReaderFactory(scalar_store, batched=True)
    batches = []
    with MeshDataLoader(factory, batch_size=80, seed=9, num_epochs=2,
                        num_hosts=4) as loader:
        for batch in loader:
            batches.append(np.asarray(batch["id"]).tolist())
    assert len(batches) == 20  # 2 epochs x 800/80
    e1 = [i for b in batches[:10] for i in b]
    e2 = [i for b in batches[10:] for i in b]
    assert sorted(e1) == sorted(e2) == list(range(800))
    assert e1 != e2  # seed + epoch reshuffles the shard plan


# --------------------------------------------- acceptance e2e: host loss
def test_killed_host_reshards_exactly_once(scalar_store):
    """The acceptance e2e: kill a host mid-epoch; after the reshard
    barrier every row group lands exactly once, the loss and reassignment
    are visible in mesh telemetry, and the mid-epoch cursor stays VALID
    (PR 10 fold-in, docs/mesh.md "Cursors after a reshard"): recovery
    deliveries ride the cursor's ``recovered`` ordinal set instead of the
    per-cursor refusal PR 7 shipped."""
    factory = MeshReaderFactory(scalar_store, batched=True)
    loader = MeshDataLoader(factory, batch_size=80, seed=0, num_epochs=1,
                            drop_last=False, pad_last=True)
    ids = []
    with loader:
        it = iter(loader)
        ids.extend(_valid_rows(next(it)))
        loader.kill_host(5)
        for batch in it:
            ids.extend(_valid_rows(batch))
        report = loader.mesh_report()
        snap = loader.telemetry.snapshot()
        state = loader.state_dict()
    counts = {}
    for i in ids:
        counts[i] = counts.get(i, 0) + 1
    assert sorted(counts) == list(range(800))
    assert all(v == 1 for v in counts.values()), "duplicated rows"
    assert report["reshard_events"] == 1
    assert [lost["host"] for lost in report["hosts_lost"]] == [5]
    assert snap["counters"]["mesh.hosts_lost"] == 1
    assert any(e["payload"]["host"] == 5
               for e in snap["events"]["mesh.reshard"])
    # The post-reshard cursor is a real cursor, with reshard provenance;
    # here the epoch COMPLETED, so it is the next epoch's clean start.
    assert state is not None and state.get("mesh") is True


def test_killed_host_never_loses_rows_with_nonfifo_pool(scalar_store):
    """workers_count=2 per host: delivery is out of ventilation order, so
    reshard accounting degrades to the watermark — bounded duplication is
    allowed, LOSS never is (in particular a group pulled but not yet
    enqueued when the kill lands must stay in the reassigned range)."""
    factory = MeshReaderFactory(scalar_store, batched=True, workers_count=2)
    assert not factory.fifo_delivery
    loader = MeshDataLoader(factory, batch_size=80, seed=1, num_epochs=1,
                            drop_last=False, pad_last=True,
                            host_queue_depth=1)
    ids = []
    with loader:
        it = iter(loader)
        ids.extend(_valid_rows(next(it)))
        loader.kill_host(4)
        for batch in it:
            ids.extend(_valid_rows(batch))
    assert sorted(set(ids)) == list(range(800)), "rows lost on reshard"


def test_strict_mode_raises_on_host_loss(scalar_store):
    factory = MeshReaderFactory(scalar_store, batched=True)
    with MeshDataLoader(factory, batch_size=80, seed=0, num_epochs=1,
                        strict=True) as loader:
        it = iter(loader)
        next(it)
        loader.kill_host(1)
        with pytest.raises(MeshHostLostError, match="host 1"):
            for _ in it:
                pass


def test_reader_failure_is_a_host_loss(scalar_store, tmp_path):
    """A host whose READER dies (here: beyond-budget injected faults, the
    PR 2 failure detector) reshards exactly like a kill."""
    from petastorm_tpu.resilience import (ExponentialBackoff, FaultPlan,
                                          FaultSpec, RetryPolicy)

    class FaultyFactory(MeshReaderFactory):
        """Injects a permanent read fault into host 2's PRIMARY reader
        only — recovery readers (strict subsets of that shard, spread to
        survivors) read clean, like a failed host whose disk died."""

        def __init__(self, url, fault_shard_ordinals):
            super().__init__(url, batched=True)
            self._fault_shard = list(fault_shard_ordinals)

        def __call__(self, rowgroup_subset):
            kwargs = dict(self.reader_kwargs)
            if list(rowgroup_subset) == self._fault_shard:
                kwargs["fault_plan"] = FaultPlan(
                    [FaultSpec(site="rowgroup.read", kind="ioerror",
                               rate=1.0)], seed=0)
                kwargs["retry_policy"] = RetryPolicy(
                    max_attempts=2, seed=0,
                    backoff=ExponentialBackoff(base=0.001, cap=0.002))
            return make_batch_reader(
                self.dataset_url, rowgroup_subset=list(rowgroup_subset),
                shuffle_row_groups=False, num_epochs=1, **kwargs)

    probe = MeshReaderFactory(scalar_store, batched=True)
    plan = MeshDataLoader(probe, batch_size=80, seed=None,
                          num_hosts=4).epoch_plan(0)
    factory = FaultyFactory(scalar_store, plan[2])
    ids = []
    with MeshDataLoader(factory, batch_size=80, seed=None, num_epochs=1,
                        num_hosts=4, drop_last=False,
                        pad_last=True) as loader:
        for batch in loader:
            ids.extend(_valid_rows(batch))
        report = loader.mesh_report()
    # Host 2 dies on its first group (exhausting the retry budget); its
    # whole shard re-reads exactly once through the survivors.
    assert sorted(ids) == list(range(800))
    assert report["reshard_events"] >= 1
    assert [lost["host"] for lost in report["hosts_lost"]] == [2]


# --------------------------------------------------------------- NGram/llm
def test_mesh_ngram_dense_windows(token_store):
    import jax
    from petastorm_tpu.ngram import NGram

    ngram = NGram({o: ["ts", "token"] for o in range(32)},
                  delta_threshold=1, timestamp_field="ts",
                  timestamp_overlap=False, dense=True)
    factory = MeshReaderFactory(token_store, batched=False,
                                schema_fields=ngram)
    assert not factory.fifo_delivery  # row reader: watermark accounting
    windows = []
    with MeshDataLoader(factory, batch_size=8, seed=0,
                        num_epochs=1) as loader:
        for batch in loader:
            assert isinstance(batch["token"], jax.Array)
            assert batch["token"].shape == (8, 32)
            assert len(batch["token"].sharding.device_set) == 8
            windows.append(np.asarray(batch["ts"])[:, 0].tolist())
    starts = sorted(s for b in windows for s in b)
    assert starts == [i * 32 for i in range(16)]  # every window, once


def test_mesh_ngram_requires_dense(token_store):
    from petastorm_tpu.ngram import NGram
    ngram = NGram({o: ["ts", "token"] for o in range(32)},
                  delta_threshold=1, timestamp_field="ts",
                  timestamp_overlap=False, dense=False)
    factory = MeshReaderFactory(token_store, batched=False,
                                schema_fields=ngram)
    with MeshDataLoader(factory, batch_size=8, num_epochs=1) as loader:
        with pytest.raises(ValueError, match="dense=True"):
            next(iter(loader))


# ------------------------------------------------------------- telemetry
def test_mesh_telemetry_and_stall_gauge(scalar_store):
    factory = MeshReaderFactory(scalar_store, batched=True)
    with MeshDataLoader(factory, batch_size=80, seed=1, num_epochs=1,
                        num_hosts=4) as loader:
        for _ in loader:
            time.sleep(0.002)  # a "device step", so stall% is meaningful
        snap = loader.telemetry.snapshot()
        report = loader.mesh_report()
    assert snap["gauges"]["mesh.hosts"] == 4
    assert "loader.input_stall_pct" in snap["gauges"]
    assert snap["gauges"]["loader.input_stall_pct"] is not None
    for h in range(4):
        assert f"mesh.host{h}.rowgroups" in snap["counters"]
    assert set(report["per_host"]) == {0, 1, 2, 3}
    for host_stats in report["per_host"].values():
        assert 0.0 <= host_stats["input_stall_pct"] <= 100.0
    assert report["host_skew_s"] >= 0.0


def test_one_shot_warning_memo_fires_once_per_process(scalar_store):
    """The per-process memo (reader.py _warn_once): a mesh epoch builds
    one reader per host, so a process-wide caveat must not repeat per
    reader."""
    import warnings as warnings_mod
    _reset_one_shot_warnings()

    def build():
        reader = make_batch_reader(scalar_store, reader_pool_type="process",
                                   workers_count=1, readahead_depth=2,
                                   shuffle_row_groups=False)
        reader.stop()
        reader.join()

    with warnings_mod.catch_warnings(record=True) as caught:
        warnings_mod.simplefilter("always")
        build()
        build()
    hits = [w for w in caught if "readahead_depth" in str(w.message)]
    assert len(hits) == 1, "one-shot warning fired once per reader"
    _reset_one_shot_warnings()
    with warnings_mod.catch_warnings(record=True) as caught:
        warnings_mod.simplefilter("always")
        build()
    assert any("readahead_depth" in str(w.message) for w in caught)


# ---------------------------------------------------------------- resume
def test_mesh_resume_state_restores_per_host_position(scalar_store):
    """Stop after k batches, rebuild from state_dict(): the remainder of
    the epoch arrives with no loss (and, with the group-aligned batch
    used here, no duplication either)."""
    factory = MeshReaderFactory(scalar_store, batched=True)
    first = []
    with MeshDataLoader(factory, batch_size=80, seed=4, num_hosts=4,
                        num_epochs=1) as loader:
        it = iter(loader)
        for _ in range(3):
            first.extend(np.asarray(next(it)["id"]).tolist())
        state = loader.state_dict()
    assert state["epoch"] == 0 and state["num_hosts"] == 4
    assert sum(state["hosts"].values()) >= len(first) // 20 - 4
    rest = _epoch_ids(factory, batch_size=80, seed=4, num_hosts=4,
                      num_epochs=1, resume_state=state)
    assert sorted(first + rest) == list(range(800))


def test_mesh_resume_rejects_changed_plan(scalar_store):
    factory = MeshReaderFactory(scalar_store, batched=True)
    with MeshDataLoader(factory, batch_size=80, seed=4, num_hosts=4,
                        num_epochs=1) as loader:
        next(iter(loader))
        state = loader.state_dict()
    with pytest.raises(ValueError, match="do not transfer"):
        MeshDataLoader(factory, batch_size=80, seed=4, num_hosts=8,
                       num_epochs=1, resume_state=state)


def test_mesh_resume_epoch_index_across_epochs(scalar_store):
    """The cursor tracks the epoch ordinal: consume exactly one full
    epoch of a two-epoch run, resume, and get exactly the second epoch."""
    factory = MeshReaderFactory(scalar_store, batched=True)
    with MeshDataLoader(factory, batch_size=80, seed=6, num_hosts=4,
                        num_epochs=2) as loader:
        it = iter(loader)
        epoch1 = [np.asarray(next(it)["id"]).tolist() for _ in range(10)]
        # one more pull so the epoch-1-complete cursor is delivered
        first_of_e2 = np.asarray(next(it)["id"]).tolist()
        state = loader.state_dict()
    assert state["epoch"] == 1
    resumed = _epoch_ids(factory, batch_size=80, seed=6, num_hosts=4,
                         num_epochs=1, resume_state=state)
    flat1 = [i for b in epoch1 for i in b]
    assert sorted(flat1) == list(range(800))
    assert sorted(first_of_e2 + resumed) == list(range(800))
