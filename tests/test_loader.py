"""Host loader contracts: static shapes, padding masks, per-replica shard
assembly in mesh order (SURVEY.md §2b #12/#14 consequences)."""

import numpy as np
import pytest

from tpuddp.data import DataLoader, ShardedDataLoader, SyntheticClassification
from tpuddp.parallel import DistributedSampler, make_mesh


def test_dataloader_batches_and_final_padding():
    ds = SyntheticClassification(n=10, shape=(4,), seed=0)
    loader = DataLoader(ds, batch_size=4)
    batches = list(loader)
    assert len(batches) == len(loader) == 3
    x, y, w = batches[-1]
    assert x.shape == (4, 4) and y.shape == (4,) and w.shape == (4,)
    np.testing.assert_array_equal(w, [1, 1, 0, 0])
    assert all(b[2].sum() == 4 for b in batches[:-1])


def test_dataloader_drop_last():
    ds = SyntheticClassification(n=10, shape=(4,))
    loader = DataLoader(ds, batch_size=4, drop_last=True)
    assert len(list(loader)) == 2


def test_dataloader_shuffle_reshuffles_with_epoch():
    ds = SyntheticClassification(n=32, shape=(2,))
    loader = DataLoader(ds, batch_size=32, shuffle=True, seed=5)
    loader.set_epoch(0)
    (x0, y0, _), = list(loader)
    loader.set_epoch(1)
    (x1, y1, _), = list(loader)
    assert not np.array_equal(y0, y1)
    loader.set_epoch(0)
    (x0b, y0b, _), = list(loader)
    np.testing.assert_array_equal(y0, y0b)


def test_dataloader_with_sampler_shards():
    ds = SyntheticClassification(n=64, shape=(2,))
    loaders = [
        DataLoader(ds, batch_size=8, sampler=DistributedSampler(64, 4, r, shuffle=False))
        for r in range(4)
    ]
    assert all(len(l) == 2 for l in loaders)
    seen = []
    for l in loaders:
        for x, y, w in l:
            assert w.sum() == 8
            seen.extend(y.tolist())
    assert sorted(seen) == sorted(ds.labels.tolist())


def test_sharded_loader_local_batch_layout(cpu_devices):
    mesh = make_mesh(cpu_devices[:4])
    ds = SyntheticClassification(n=64, shape=(2,), seed=1)
    loader = ShardedDataLoader(ds, batch_size=4, mesh=mesh, shuffle=False)
    assert loader.world_size == 4
    assert loader.local_ranks == [0, 1, 2, 3]
    assert len(loader) == 4  # 16 per replica / 4
    x, y, w = next(iter(loader))
    assert x.shape == (16, 2)
    # replica r's first sample is global index r (stride-4 sharding, no shuffle)
    np.testing.assert_array_equal(y[::4], ds.labels[[0, 1, 2, 3]])


def test_sharded_loader_covers_dataset_disjointly(cpu_devices):
    mesh = make_mesh(cpu_devices)
    ds = SyntheticClassification(n=128, shape=(2,), seed=2)
    loader = ShardedDataLoader(ds, batch_size=4, mesh=mesh, shuffle=True, seed=3)
    loader.set_epoch(0)
    idx_seen = []
    for x, y, w in loader:
        assert w.sum() == 32  # all real, 128 divisible
        idx_seen.extend(y.tolist())
    assert len(idx_seen) == 128


def test_sharded_loader_padding_mask(cpu_devices):
    mesh = make_mesh(cpu_devices)
    ds = SyntheticClassification(n=100, shape=(2,))
    loader = ShardedDataLoader(ds, batch_size=8, mesh=mesh, shuffle=False)
    # 100/8 replicas -> 13 samples each -> 2 steps (8 + 5real/3pad)
    assert len(loader) == 2
    batches = list(loader)
    _, _, w_last = batches[-1]
    assert w_last.sum() == 8 * 5  # 5 real per replica in final batch
    total_real = sum(b[2].sum() for b in batches)
    assert total_real == 104  # 100 + 4 wrap-pad duplicates (sampler padding)


def test_probe_fingerprint_mentions_each_replica(cpu_devices):
    mesh = make_mesh(cpu_devices[:2])
    ds = SyntheticClassification(n=16, shape=(8,))
    loader = ShardedDataLoader(ds, batch_size=4, mesh=mesh, shuffle=False)
    x, _, _ = next(iter(loader))
    s = loader.probe_fingerprint(x)
    assert "replica 0" in s and "replica 1" in s


def _loader_under_test(kind, cpu_devices):
    ds = SyntheticClassification(n=37, shape=(4, 4, 3), seed=2)
    if kind == "single":
        return DataLoader(ds, batch_size=8, shuffle=True, seed=3)
    return ShardedDataLoader(ds, 4, make_mesh(cpu_devices[:2]), shuffle=True, seed=3)


@pytest.mark.parametrize("kind", ["single", "sharded"])
def test_loader_opens_its_own_spans_under_a_handed_tracer(kind, cpu_devices):
    """Handed a tracer and a parent, a loader brackets what it does where it
    does it: the epoch's order once an iteration, the row gather and the
    padding once a batch, all of kind ``load`` under the parent; the batches
    are bitwise the untraced ones; handed None again it opens nothing."""
    from tpuddp.observability import trace as trace_lib

    loader = _loader_under_test(kind, cpu_devices)
    loader.set_epoch(1)
    want = list(loader)
    tracer = trace_lib.Tracer("train", process_index=0)
    parent = tracer.start_span("epoch 1", trace_lib.KIND_EPOCH)
    loader.set_tracer(tracer, parent)
    got = list(loader)
    loader.set_tracer(None)
    tracer.end_span(parent)
    assert len(got) == len(want) == len(loader)
    for a, b in zip(got, want):
        for x, y in zip(a, b):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(x, y)
    spans = [s for s in tracer.endpoint_payload(limit=None)["spans"] if s is not None]
    names = [s["name"] for s in spans if s["name"] != "epoch 1"]
    assert names == ["loader_order"] + ["loader_gather", "loader_pad"] * len(loader)
    assert {(s["kind"], s["parent_id"]) for s in spans if s["name"] != "epoch 1"} == {
        ("load", parent.span_id)
    }
    assert {s["tid"] for s in spans} == {"train"}  # this thread: the parent's row
    done = tracer.completed
    assert len(list(loader)) == len(want) and tracer.completed == done
