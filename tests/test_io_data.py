"""DataLoader/Dataset/Sampler tests (dataloader suites of the reference)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import (
    Dataset, IterableDataset, TensorDataset, ComposeDataset, ChainDataset,
    ConcatDataset, Subset, random_split, BatchSampler, RandomSampler,
    SequenceSampler, DistributedBatchSampler, DataLoader, default_collate_fn,
)


class RangeDataset(Dataset):
    def __init__(self, n=32):
        self.n = n

    def __getitem__(self, i):
        return (np.full((4,), i, dtype="float32"), np.int64(i % 4))

    def __len__(self):
        return self.n


class StreamDataset(IterableDataset):
    def __init__(self, n=10):
        self.n = n

    def __iter__(self):
        for i in range(self.n):
            yield (np.full((2,), i, dtype="float32"), np.int64(i))


def test_tensor_dataset():
    xs = np.arange(12).reshape(6, 2).astype("float32")
    ys = np.arange(6)
    ds = TensorDataset([xs, ys])
    assert len(ds) == 6
    x, y = ds[2]
    np.testing.assert_allclose(x, xs[2])


def test_compose_chain_concat_subset_split():
    d = RangeDataset(8)
    comp = ComposeDataset([d, d])
    assert len(comp[0]) == 4
    cat = ConcatDataset([d, RangeDataset(4)])
    assert len(cat) == 12
    np.testing.assert_allclose(cat[10][0], np.full((4,), 2))
    sub = Subset(d, [3, 5])
    assert float(sub[1][0][0]) == 5
    a, b = random_split(d, [6, 2])
    assert len(a) == 6 and len(b) == 2
    chain = ChainDataset([StreamDataset(3), StreamDataset(2)])
    assert len(list(chain)) == 5


def test_batch_sampler_shapes():
    d = RangeDataset(10)
    bs = BatchSampler(d, batch_size=4, drop_last=False)
    batches = list(bs)
    assert [len(b) for b in batches] == [4, 4, 2]
    assert len(bs) == 3
    bs2 = BatchSampler(d, batch_size=4, drop_last=True)
    assert len(bs2) == 2


def test_random_sampler_permutes():
    d = RangeDataset(16)
    idx = list(RandomSampler(d))
    assert sorted(idx) == list(range(16))


def test_distributed_batch_sampler_shards():
    d = RangeDataset(16)
    seen = []
    for rank in range(4):
        s = DistributedBatchSampler(d, batch_size=2, num_replicas=4,
                                    rank=rank)
        for batch in s:
            seen.extend(batch)
    assert sorted(seen) == list(range(16))


def test_dataloader_basic():
    loader = DataLoader(RangeDataset(16), batch_size=4)
    batches = list(loader)
    assert len(batches) == 4
    x, y = batches[0]
    assert x.shape == [4, 4]
    assert y.shape == [4]
    assert isinstance(x, paddle.Tensor)


def test_dataloader_shuffle_covers_all():
    loader = DataLoader(RangeDataset(16), batch_size=4, shuffle=True)
    vals = []
    for x, y in loader:
        vals.extend(x.numpy()[:, 0].astype(int).tolist())
    assert sorted(vals) == list(range(16))


def test_dataloader_iterable_dataset():
    loader = DataLoader(StreamDataset(10), batch_size=4)
    shapes = [x.shape[0] for x, _ in loader]
    assert shapes == [4, 4, 2]


def test_dataloader_multiworker_order_and_coverage():
    loader = DataLoader(RangeDataset(32), batch_size=4, num_workers=2)
    vals = []
    for x, y in loader:
        vals.extend(x.numpy()[:, 0].astype(int).tolist())
    assert vals == list(range(32))  # order preserved despite 2 workers


def test_dataloader_worker_error_surfaces():
    class Bad(Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            if i == 5:
                raise ValueError("boom")
            return np.zeros(2, "float32")

    loader = DataLoader(Bad(), batch_size=2, num_workers=2)
    with pytest.raises(RuntimeError, match="worker error"):
        list(loader)


def test_dataloader_dp_sharded_batches():
    from paddle_tpu.parallel import init_mesh
    init_mesh({"dp": -1})
    loader = DataLoader(RangeDataset(32), batch_size=8)
    x, _ = next(iter(loader))
    assert len(x._value.sharding.device_set) >= 1


def test_dataloader_multiworker_empty_yield():
    """drop_last with dataset smaller than batch: zero batches, no hang."""
    loader = DataLoader(RangeDataset(2), batch_size=8, drop_last=True,
                        num_workers=2, timeout=10)
    assert list(loader) == []


def test_collate_nested_dict():
    batch = [{"a": np.ones(2, "float32"), "b": 1},
             {"a": np.zeros(2, "float32"), "b": 2}]
    out = default_collate_fn(batch)
    assert out["a"].shape == (2, 2)
    assert out["b"].tolist() == [1, 2]


def test_dataloader_from_generator():
    """Legacy reader.py:425 generator-fed loader (three setter flavors)."""
    from paddle_tpu.io import DataLoader
    loader = DataLoader.from_generator(capacity=8)

    def gen():
        for i in range(3):
            yield np.full((4, 2), i, "float32"), np.full((4,), i, "int64")

    loader.set_batch_generator(gen)
    out = [(float(x.numpy()[0, 0]), int(y.numpy()[0])) for x, y in loader]
    assert out == [(0.0, 0), (1.0, 1), (2.0, 2)]

    loader2 = DataLoader.from_generator()

    def sgen():
        for i in range(7):
            yield np.full((2,), i, "float32"), np.int64(i)

    loader2.set_sample_generator(sgen, batch_size=3, drop_last=True)
    shapes = [list(x.shape) for x, y in loader2]
    assert shapes == [[3, 2], [3, 2]]

    loader3 = DataLoader.from_generator()

    def slgen():
        for i in range(2):
            yield [(np.full((2,), i, "float32"),) for _ in range(4)]

    loader3.set_sample_list_generator(slgen)
    batches = [x[0] for x in loader3]
    assert [list(b.shape) for b in batches] == [[4, 2], [4, 2]]


def test_static_save_load_vars(tmp_path):
    import paddle_tpu.static as static
    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [2, 3], "float32")
            out = static.nn.fc(x, 2)
        exe = static.Executor()
        exe.run(startup)
        params = [v for v in main.list_vars() if v.persistable]
        static.save_vars(exe, str(tmp_path), main, vars=params)
        import numpy as _np
        ref = _np.asarray(static.global_scope().find_var(params[0].name))
        static.global_scope().set_var(params[0].name,
                                      _np.zeros_like(ref))
        static.load_vars(exe, str(tmp_path), main, vars=params)
        got = _np.asarray(static.global_scope().find_var(params[0].name))
        assert _np.allclose(got, ref)
    finally:
        paddle.disable_static()


@pytest.mark.parametrize("ends_with_error", [False, True])
def test_double_buffer_end_marker_survives_a_slow_consumer(ends_with_error):
    """A consumer that holds the queue full for longer than the producer
    used to wait with its end marker (a first step that compiles for
    seconds does): the marker, and a producer's error, must still arrive;
    the consumer once waited on the emptied queue for ever."""
    import threading
    import time
    from paddle_tpu.io.dataloader import _double_buffered

    def items():
        yield from range(3)          # the last two fill the queue
        if ends_with_error:
            raise ValueError("the source failed")

    got, done = [], threading.Event()

    def consume():
        try:
            for i, x in enumerate(_double_buffered(items, maxsize=2)):
                if i == 0:
                    time.sleep(1.6)     # queue full, producer finished
                got.append(x)
        except ValueError as e:
            got.append(str(e))
        done.set()

    threading.Thread(target=consume, daemon=True).start()
    assert done.wait(15), "the consumer never saw the end of the stream"
    assert got == [0, 1, 2] + (["the source failed"] * ends_with_error)
