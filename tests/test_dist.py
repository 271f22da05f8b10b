"""Distributed data-parallel: DistOpt strategies on an 8-device CPU mesh.

Improves on ref test/python/test_dist.py, which can only assert at
world_size 1 without a cluster (SURVEY.md §4): here the mesh is real
(8 forced host devices), so allreduce numerics are exercised for real.
"""

import numpy as np
import pytest

from singa_tpu import layer, model, opt, tensor
from singa_tpu.parallel import data_parallel_mesh, make_mesh
from singa_tpu.parallel.communicator import Communicator


class MLP(model.Model):
    def __init__(self, hidden=16, classes=4):
        super().__init__()
        self.l1 = layer.Linear(hidden)
        self.relu = layer.ReLU()
        self.l2 = layer.Linear(classes)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.l2(self.relu(self.l1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self._optimizer(loss)
        return out, loss


class MLPHalf(MLP):
    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self._optimizer.backward_and_update_half(loss)
        return out, loss


class MLPSparse(MLP):
    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self._optimizer.backward_and_sparse_update(loss, spars=0.25,
                                                   topK=True, corr=True)
        return out, loss


class MLPPartial(MLP):
    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self._optimizer.backward_and_partial_update(loss, num_partitions=2)
        return out, loss


@pytest.fixture
def data(rng):
    X = rng.randn(32, 10).astype(np.float32)
    Y = np.argmax(X @ rng.randn(10, 4).astype(np.float32), 1).astype(np.int32)
    return X, Y


@pytest.fixture
def mesh():
    return data_parallel_mesh(8)


def _run(cls, dev, mesh, X, Y, steps=40, lr=0.2):
    m = cls()
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=lr, momentum=0.9), mesh=mesh))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True)
    losses = []
    for _ in range(steps):
        out, loss = m(tx, ty)
        losses.append(float(loss.numpy()))
    return m, losses, out


def test_world_size(mesh):
    assert opt.DistOpt(opt.SGD(0.1), mesh=mesh).world_size == 8


@pytest.mark.parametrize("cls", [MLP, MLPHalf, MLPSparse, MLPPartial],
                         ids=["plain", "half", "sparse_topk", "partial"])
def test_strategies_converge(cls, dev, mesh, data):
    X, Y = data
    m, losses, out = _run(cls, dev, mesh, X, Y)
    assert losses[-1] < 0.4 * losses[0], losses
    assert out.shape == (32, 4)  # the global batch's shape, left sharded


# ---- how the data-parallel step hands its outputs back ----------------------

def _dp_twin(cls, dev, mesh, X, lr=0.1, **kw):
    """A single-device model and `cls` under DistOpt on the same initial
    weights."""
    tx = tensor.from_numpy(X, dev)
    m1 = MLP(**kw)
    m1.set_optimizer(opt.SGD(lr=lr))
    m1.compile([tx], is_train=True, use_graph=True)
    w0 = {k: v.numpy().copy() for k, v in m1.get_params().items()}
    m2 = cls(**kw)
    m2.set_optimizer(opt.DistOpt(opt.SGD(lr=lr), mesh=mesh))
    m2.compile([tx], is_train=True, use_graph=True)
    m2.set_params(w0)
    return m1, m2


def test_dp_step_runs_no_gather_and_leaves_the_batch_sharded(dev, mesh, data):
    """The step's text holds no all-gather; its batch output is one global
    array, each device holding the rows it computed; the loss replicated."""
    from jax.sharding import PartitionSpec as P
    X, Y = data
    m, _, out = _run(MLP, dev, mesh, X, Y, steps=1)
    hlo = m.lower_step().as_text()
    assert "all_reduce" in hlo or "all-reduce" in hlo  # the dialect's names
    assert "all_gather" not in hlo and "all-gather" not in hlo
    assert out.shape == (32, 4)
    assert out.data.sharding.spec == P("data")
    assert out.data.sharding.mesh.shape == mesh.shape
    shards = out.data.addressable_shards
    assert len(shards) == 8
    whole = out.numpy()
    for s in shards:
        assert s.data.shape == (4, 4)
        np.testing.assert_array_equal(np.asarray(s.data), whole[s.index])
    assert sorted(s.index[0].start or 0 for s in shards) \
        == list(range(0, 32, 4))
    _, loss = m(tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev))
    assert loss.data.sharding.spec == P()


@pytest.mark.parametrize("cls", [MLP, MLPHalf, MLPSparse, MLPPartial],
                         ids=["plain", "half", "sparse_topk", "partial"])
def test_dp_first_output_equals_single_device(cls, dev, mesh, data):
    """A read of the first step's batch output gives the global batch, row
    for row what one device computes on the same weights (the forward
    comes before any strategy acts)."""
    X, Y = data
    m1, m2 = _dp_twin(cls, dev, mesh, X)
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    out1, loss1 = m1(tx, ty)
    out2, loss2 = m2(tx, ty)
    assert out2.shape == out1.shape == (32, 4)
    np.testing.assert_allclose(out2.numpy(), out1.numpy(),
                               rtol=1e-5, atol=1e-6)
    assert abs(float(loss1.numpy()) - float(loss2.numpy())) < 1e-5


class MLPTwoOutputs(MLP):
    """Two batch outputs around the scalar: the order must come back."""

    def train_one_batch(self, x, y):
        hidden = self.relu(self.l1(x))
        out = self.l2(hidden)
        loss = self.loss_fn(out, y)
        self._optimizer(loss)
        return hidden, loss, out


def _step_outputs_gauge():
    from singa_tpu import observe
    g = observe.get_registry().get("singa_step_outputs")
    return {k: int(g.value(kind=k)) for k in ("batch_sharded",
                                              "mean_reduced")}


def test_step_outputs_gauge_follows_the_traced_step(dev, mesh, data):
    X, Y = data
    _run(MLP, dev, mesh, X, Y, steps=1)
    assert _step_outputs_gauge() == {"batch_sharded": 1, "mean_reduced": 1}
    m1, m2 = _dp_twin(MLPTwoOutputs, dev, mesh, X)
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    hidden, loss, out = m2(tx, ty)
    assert _step_outputs_gauge() == {"batch_sharded": 2, "mean_reduced": 1}
    assert (hidden.shape, loss.shape, out.shape) == ((32, 16), (), (32, 4))
    out1, loss1 = m1(tx, ty)
    np.testing.assert_allclose(out.numpy(), out1.numpy(),
                               rtol=1e-5, atol=1e-6)
    assert abs(float(loss.numpy()) - float(loss1.numpy())) < 1e-5


@pytest.mark.parametrize("is_train", [True, False], ids=["train", "eval"])
def test_dp_output_feeds_a_later_call(is_train, dev, mesh, data):
    """A step's sharded output as the next step's input, or an
    is_train=False call's: the same result as its host copy gives."""
    X, Y = data
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    # 10 classes, so that an output has an input's shape; the single-device
    # twin takes the same step and then the host copy
    m1, m2 = _dp_twin(MLP, dev, mesh, X, classes=10)
    out1, _ = m1(tx, ty)
    out2, _ = m2(tx, ty)
    assert out2.data.sharding.spec[0] == "data"
    host = tensor.from_numpy(out1.numpy(), dev)
    if is_train:
        got, got_loss = m2(out2, ty)
        want, want_loss = m1(host, ty)
        assert abs(float(got_loss.numpy()) - float(want_loss.numpy())) < 1e-5
    else:
        m1.eval()
        m2.eval()
        got, want = m2(out2), m1(host)
    assert got.shape == (32, 10)
    np.testing.assert_allclose(got.numpy(), want.numpy(),
                               rtol=1e-4, atol=1e-5)


def test_dp_output_on_a_data_tp_mesh(dev, rng):
    """With a tp axis in the mesh the batch output is sharded over `data`
    and replicated over `tp`."""
    from jax.sharding import PartitionSpec as P

    class TPMLP(model.Model):
        def __init__(self):
            super().__init__()
            self.l1 = layer.Linear(16, tp_axis="tp", tp_mode="column")
            self.relu = layer.ReLU()
            self.l2 = layer.Linear(4, tp_axis="tp", tp_mode="row")
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.l2(self.relu(self.l1(x)))

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss_fn(out, y)
            self._optimizer(loss)
            return out, loss

    mesh = make_mesh({"data": 2, "tp": 4})
    X = rng.randn(16, 10).astype(np.float32)
    Y = np.argmax(X @ rng.randn(10, 4).astype(np.float32), 1) \
        .astype(np.int32)
    m = TPMLP()
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.1), axis="data", mesh=mesh))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True)
    w = {k: v.numpy().copy() for k, v in m.get_params().items()}
    out, _ = m(tx, ty)
    assert out.shape == (16, 4)
    assert out.data.sharding.spec == P("data")
    rows = {}
    for s in out.data.addressable_shards:
        assert s.data.shape == (8, 4)
        rows.setdefault(s.index[0].start or 0, []).append(
            np.asarray(s.data))
    assert sorted(rows) == [0, 8]
    for copies in rows.values():       # one copy on each of the 4 tp ranks
        assert len(copies) == 4
        for c in copies[1:]:
            np.testing.assert_array_equal(c, copies[0])
    want = np.maximum(X @ w["l1.W"] + w["l1.b"], 0) @ w["l2.W"] + w["l2.b"]
    np.testing.assert_allclose(out.numpy(), want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("addressable", [True, False],
                         ids=["addressable", "spans_processes"])
def test_numpy_gathers_only_what_is_not_fully_addressable(
        addressable, dev, monkeypatch):
    """Tensor.numpy() picks its branch by `is_fully_addressable` and by
    nothing else: an array whose rows live on other processes' devices
    (a data-parallel step's batch output under jax.distributed) is gathered
    at the read, tiled; any other is converted in place. One process
    cannot hold such an array, so a stand-in carries the flag;
    examples/multihost/ckpt_2proc.py is the two-process run."""
    from jax.experimental import multihost_utils

    class StandIn:
        is_fully_addressable = addressable
        converted = 0

        def __array__(self, dtype=None, copy=None):
            self.converted += 1
            return np.arange(6, dtype=np.float32).reshape(3, 2)

    calls = []

    def gather(a, tiled=False):
        calls.append((a, tiled))
        return np.ones((12, 2), np.float32)

    monkeypatch.setattr(multihost_utils, "process_allgather", gather)
    a = StandIn()
    got = tensor.Tensor(data=a, device=dev, requires_grad=False).numpy()
    if addressable:
        assert calls == [] and a.converted == 1
        assert got.shape == (3, 2)
    else:
        assert calls == [(a, True)] and a.converted == 0
        assert got.shape == (12, 2) and isinstance(got, np.ndarray)


def test_dp_matches_single_device(dev, mesh, data):
    """psum-mean grads over 8 shards == full-batch single device."""
    X, Y = data
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m1, m2 = _dp_twin(MLP, dev, mesh, X)
    for _ in range(3):
        _, l1 = m1(tx, ty)
        _, l2 = m2(tx, ty)
    assert abs(float(l1.numpy()) - float(l2.numpy())) < 1e-4
    for k in m1.get_params():
        assert np.allclose(m1.get_params()[k].numpy(),
                           m2.get_params()[k].numpy(), atol=1e-4), k


def test_world1_degrades_to_identity(dev, rng):
    """Reference test_dist.py asserts at world_size 1; same here."""
    comm = Communicator()
    assert comm.world_size == 1
    x = np.asarray(rng.randn(8).astype(np.float32))
    import jax.numpy as jnp
    assert np.allclose(np.asarray(comm.all_reduce(jnp.asarray(x))), x)
    out, res = comm.sparse_all_reduce_topk(jnp.asarray(x), 0.25)
    assert np.allclose(np.asarray(out) + np.asarray(res), x, atol=1e-6)


def test_threshold_matches_dense_and_reconstructs(dev, rng, mesh):
    """Packed threshold allreduce == dense psum of thresholded tensors
    (capacity ample), and out+residual reconstructs each shard's input."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    comm = Communicator(mesh=mesh)
    x = rng.randn(8, 64).astype(np.float32)
    thr = 0.8

    def f(xs):
        out, res = comm.sparse_all_reduce_threshold(xs, thr,
                                                    capacity_frac=0.9)
        dense = jax.lax.psum(jnp.where(jnp.abs(xs) >= thr, xs, 0.0), "data")
        return out, res, dense

    out, res, dense = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=1e-5)
    # error-feedback identity: residual + sent == input per shard
    sent = x - np.asarray(res)
    mask = np.abs(x) >= thr
    np.testing.assert_allclose(sent, np.where(mask, x, 0.0), atol=1e-6)


def test_threshold_payload_is_packed(dev, rng, mesh):
    """The wire format must be (index, value) pairs of capacity size —
    no dense all-reduce at all (ref communicator.cc:667-688 semantics)."""
    import jax
    from jax.sharding import PartitionSpec as P
    comm = Communicator(mesh=mesh)
    n = 4096
    cap = max(1, int(n // 8 * 0.05))  # per-shard elements / capacity_frac

    def f(xs):
        out, _ = comm.sparse_all_reduce_threshold(xs, 0.5,
                                                  capacity_frac=0.05)
        return out

    hlo = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False)).lower(
            np.zeros((n,), np.float32)).as_text()
    assert "all_reduce" not in hlo and "all-reduce" not in hlo, \
        "threshold path must not psum dense"
    assert "all_gather" in hlo
    # gathered buffers are capacity-sized, not shard-sized
    assert f"8x{cap}x" in hlo


from singa_tpu.utils import dense_allreduce_types as _dense_allreduce_types


def test_sparse_step_hlo_is_packed(dev, mesh, data):
    """Wire-level guarantee for strategy 4 THROUGH the compiled Model step:
    the executable's gradient collectives are capacity-
    sized all-gathers of (index, value) pairs — k = n*spars elements per
    shard — and NO param-shaped dense all-reduce exists. Fails if anyone
    regresses the sparse path to dense (ref communicator.cc:619-719)."""
    X, Y = data
    m, _, _ = _run(MLPSparse, dev, mesh, X, Y, steps=2)
    hlo = m.lower_step().as_text()
    assert "stablehlo.all_reduce" in hlo or "all-reduce" in hlo  # sanity:
    # the scalar loss pmean must be present, so the detector can't be
    # vacuously green on a renamed dialect
    dense = _dense_allreduce_types(hlo)
    assert not dense, f"dense all-reduce of {dense} in sparse step"

    # the packed payloads: top-25% of each param, gathered over 8 shards
    # l1.W (10,16): k=40; l1.b (16,): k=4; l2.W (16,4): k=16; l2.b: k=1
    for k in (40, 16, 4):
        assert f"8x{k}]" in hlo or f"8x{k}x" in hlo.replace("]", "x"), \
            f"missing capacity-{k} gathered payload"


def test_partial_update_compiles_per_partition(dev, mesh, data):
    """Strategy 3 must produce k compiled step variants whose collectives
    cover different parameter partitions (true bandwidth rotation)."""
    X, Y = data
    m, losses, _ = _run(MLPPartial, dev, mesh, X, Y, steps=5)
    tags = sorted(m._compiled_step)
    assert tags == [0, 1], tags
    # one executor a tag, each built once for the one input signature
    assert [(m._compiled_step[t].tag, len(m._compiled_step[t]))
            for t in tags] == [(0, 1), (1, 1)]
    texts = {tag: m.lower_step(tag).as_text() for tag in tags}
    for tag in tags:
        assert "all_reduce" in texts[tag] or "all-reduce" in texts[tag]
    # the synced shapes differ between partitions (l2 vs l1 params)
    assert texts[0] != texts[1]


def test_sparse_with_sharded_params(dev, rng):
    """Strategy 4 on a TP model: replicated params
    keep the packed sparse allreduce (residuals pre-created at setup so
    the per-leaf spec'd state thread stays pytree-stable), sharded params
    take the dense reduction — instead of the old hard raise."""
    from singa_tpu import layer, model, opt, tensor

    class TPMLPSparse(model.Model):
        def __init__(self):
            super().__init__()
            self.l1 = layer.Linear(16, tp_axis="tp", tp_mode="column")
            self.relu = layer.ReLU()
            self.l2 = layer.Linear(4, tp_axis="tp", tp_mode="row")
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.l2(self.relu(self.l1(x)))

        def train_one_batch(self, x, y):
            loss = self.loss_fn(self.forward(x), y)
            self._optimizer.backward_and_sparse_update(loss, spars=0.25,
                                                       topK=True)
            return loss

    mesh = make_mesh({"data": 2, "tp": 4})
    X = rng.randn(16, 10).astype(np.float32)
    Y = np.argmax(X @ rng.randn(10, 4).astype(np.float32), 1) \
        .astype(np.int32)
    m = TPMLPSparse()
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.2, momentum=0.9),
                                axis="data", mesh=mesh,
                                sparse_residuals=True))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True)
    losses = [float(m(tx, ty).numpy()) for _ in range(25)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.7, losses[::6]
    # residuals exist only for the REPLICATED params (the two biases)
    do = m._optimizer
    by_id = do.opt._params_by_id
    for pid in do._spars_order:
        assert getattr(by_id[pid], "spec", None) is None


def test_broadcast_tree(dev, rng, mesh):
    """Tree broadcast: every device ends with ROOT's
    value for any root, and the executable uses collective-permute rounds
    (ceil(log2 n) of them) — no allreduce-of-masked-zeros."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    comm = Communicator(mesh=mesh)
    x = rng.randn(8, 16).astype(np.float32)  # row i = device i's value

    for root in (0, 3, 7):
        def f(xs):
            return comm.broadcast(xs, root=root)

        out = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False))(x)
        out = np.asarray(out)
        for i in range(8):
            np.testing.assert_allclose(out[i], x[root], atol=0,
                                       err_msg=f"root={root} dev={i}")

    hlo = jax.jit(jax.shard_map(
        lambda xs: comm.broadcast(xs, root=0), mesh=mesh,
        in_specs=P("data"), out_specs=P("data"),
        check_vma=False)).lower(x).as_text()
    assert "all-reduce" not in hlo and "all_reduce" not in hlo, \
        "broadcast must not be a masked psum"
    n_perm = sum(hlo.count(p) for p in
                 ("collective-permute(", "collective-permute-start(",
                  "collective_permute\"("))
    assert 1 <= n_perm <= 3, f"expected <=log2(8) permute rounds, {n_perm}"


def test_topk_error_feedback_identity(dev, rng, mesh):
    """out + residual must reconstruct the input per shard."""
    import jax
    from jax.sharding import PartitionSpec as P
    comm = Communicator(mesh=mesh)
    x = rng.randn(8, 16).astype(np.float32)

    def f(xs):
        out, res = comm.sparse_all_reduce_topk(xs, 0.25)
        own = xs - res  # what this shard contributed
        return out, res, own

    f_sharded = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    out, res, own = f_sharded(x)
    # sum over shards of own contributions == each shard's dense result
    want = np.asarray(own).reshape(8, 16).sum(0)
    got = np.asarray(out)[0]
    assert np.allclose(got, want, atol=1e-5)
