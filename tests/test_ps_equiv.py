"""PS-equivalent subsystem: fleet datasets + distributed/host embeddings
(reference: fleet dataset tests + distributed_lookup_table semantics)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.dataset import InMemoryDataset, QueueDataset
from paddle_tpu.distributed.fleet.distributed_embedding import (
    DistributedEmbedding, HostEmbedding, HostEmbeddingTable)


@pytest.fixture
def slot_file(tmp_path):
    # 6 samples, slot0 = dense label (1 val), slot1 = sparse ids
    lines = []
    for i in range(6):
        ids = " ".join(str((i + j) % 10) for j in range(1 + i % 3))
        lines.append(f"1 {i % 2} {1 + i % 3} {ids}")
    p = tmp_path / "part-0.txt"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def test_inmemory_dataset_load_and_iterate(slot_file):
    ds = InMemoryDataset()
    ds.init(batch_size=2, thread_num=2)
    ds.set_filelist([slot_file])
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 6
    batches = list(ds)
    assert len(batches) == 3
    label, (ids, lens) = batches[0]
    assert label.shape == (2, 1)
    assert ids.shape[0] == 2 and lens.shape == (2,)


def test_inmemory_dataset_global_shuffle(slot_file):
    np.random.seed(0)
    ds = InMemoryDataset()
    ds.init(batch_size=6)
    ds.set_filelist([slot_file])
    ds.load_into_memory()
    before = list(ds)[0][0].ravel().tolist()
    ds.global_shuffle()
    after = list(ds)[0][0].ravel().tolist()
    assert sorted(before) == sorted(after)


def test_queue_dataset_streams(slot_file):
    ds = QueueDataset()
    ds.init(batch_size=2)
    ds.set_filelist([slot_file])
    assert len(list(ds)) == 3


def test_distributed_embedding_forward_grad():
    emb = DistributedEmbedding(100, 8)
    ids = paddle.to_tensor(np.array([[1, 2], [3, 1]]))
    out = emb(ids)
    assert out.shape == [2, 2, 8]
    out.sum().backward()
    g = emb.weight.grad.numpy()
    assert g[1].sum() == pytest.approx(16.0)  # id 1 twice x dim 8


def test_host_embedding_pull_push_learns():
    table = HostEmbeddingTable(50, 4, init_std=0.1, seed=1)
    ids = np.array([3, 7])
    before = table.table[ids].copy()
    grads = np.ones((2, 4), np.float32)
    table.push(ids, grads, lr=0.5)
    np.testing.assert_allclose(table.table[ids], before - 0.5, rtol=1e-6)
    # adagrad variant
    t2 = HostEmbeddingTable(10, 2, optimizer="adagrad")
    t2.push(np.array([0]), np.ones((1, 2), np.float32), lr=1.0)
    assert t2._adagrad_acc[0] > 0


def test_host_embedding_layer_end_to_end():
    paddle.seed(0)
    import paddle_tpu.nn as nn
    emb = HostEmbedding(20, 4, init_std=0.5, seed=2)
    fc = nn.Linear(4, 1)
    ids = paddle.to_tensor(np.array([1, 5, 9]))
    losses = []
    for _ in range(5):
        pulled = emb(ids)
        out = fc(pulled)
        loss = (out * out).mean()
        loss.backward()
        emb.apply_push(lr=0.5)
        for p in fc.parameters():
            p.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]


def test_host_table_save_load(tmp_path):
    t = HostEmbeddingTable(10, 3, seed=3)
    path = str(tmp_path / "table.npy")
    t.save(path)
    t2 = HostEmbeddingTable(10, 3, seed=4)
    t2.load(path)
    np.testing.assert_array_equal(t.table, t2.table)


def test_host_table_save_load_with_optimizer_state(tmp_path):
    # full server state roundtrip (reference: common_sparse_table Save/Load)
    t = HostEmbeddingTable(10, 3, seed=3, optimizer="adagrad")
    t.push(np.array([2, 7]), np.ones((2, 3), np.float32), lr=0.5)
    path = str(tmp_path / "server_state")
    t.save(path)
    t2 = HostEmbeddingTable(10, 3, seed=4, optimizer="adagrad")
    t2.load(path)
    np.testing.assert_array_equal(t.table, t2.table)
    np.testing.assert_array_equal(t._adagrad_acc, t2._adagrad_acc)


def test_host_table_push_sparse_indexed_slices():
    from paddle_tpu.core.sparse_grad import IndexedSlices
    t = HostEmbeddingTable(10, 3, seed=1)
    before = t.table.copy()
    sl = IndexedSlices(np.array([4, 4, 8]),
                       np.ones((3, 3), np.float32), (10, 3))
    t.push_sparse(sl, lr=1.0)
    np.testing.assert_allclose(t.table[4], before[4] - 2.0)  # dup summed
    np.testing.assert_allclose(t.table[8], before[8] - 1.0)
    np.testing.assert_allclose(t.table[0], before[0])


def test_c_embedding_manual_spmd_lookup():
    # explicit masked-lookup + psum primitive under shard_map over 'mp'
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import topology
    from paddle_tpu.distributed.fleet.distributed_embedding import c_embedding

    hcg = topology.HybridCommunicateGroup(dp=2, mp=4)
    mesh = hcg.mesh
    vocab, dim, n = 16, 8, 4
    rs = np.random.RandomState(0)
    w = rs.randn(vocab, dim).astype(np.float32)
    ids = rs.randint(0, vocab, (6,))

    def fn(w_local, ids_rep):
        rank = jax.lax.axis_index("mp")
        start = rank * (vocab // n)
        return c_embedding(ids_rep, w_local, "mp", start)

    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("mp", None), P()),
        out_specs=P())(jnp.asarray(w), jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), w[ids], rtol=1e-6)
    topology._HYBRID = None


def test_host_table_load_restores_optimizer_kind(tmp_path):
    t = HostEmbeddingTable(10, 3, seed=3, optimizer="adagrad")
    t.push(np.array([2]), np.ones((1, 3), np.float32), lr=0.5)
    path = str(tmp_path / "state2")
    t.save(path)
    t2 = HostEmbeddingTable(10, 3, seed=4, optimizer="sgd")
    t2.load(path)
    assert t2.optimizer == "adagrad"
    np.testing.assert_array_equal(t._adagrad_acc, t2._adagrad_acc)
