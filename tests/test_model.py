"""Model API: graph buffering (jit), eager parity, checkpointing
(pattern of ref test/python/test_model.py)."""

import numpy as np
import pytest

from singa_tpu import layer, model, opt, tensor


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


@pytest.fixture
def data(rng):
    X = rng.randn(32, 10).astype(np.float32)
    Y = np.argmax(X @ rng.randn(10, 4).astype(np.float32), 1).astype(np.int32)
    return X, Y


def _train(m, dev, X, Y, steps, use_graph):
    m.set_optimizer(opt.SGD(lr=0.2, momentum=0.9))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=use_graph)
    losses = []
    for _ in range(steps):
        out, loss = m(tx, ty)
        losses.append(float(loss.numpy()))
    return losses, out


@pytest.mark.parametrize("use_graph", [False, True])
def test_training_converges(dev, data, use_graph):
    X, Y = data
    losses, out = _train(MLP(), dev, X, Y, 40, use_graph)
    assert losses[-1] < 0.3 * losses[0]
    acc = np.mean(np.argmax(out.numpy(), 1) == Y)
    assert acc > 0.9


def test_graph_matches_eager(dev, data):
    """Same seed -> graph-mode step == eager step numerically."""
    X, Y = data
    m1, m2 = MLP(), MLP()
    m1.set_optimizer(opt.SGD(lr=0.1))
    m2.set_optimizer(opt.SGD(lr=0.1))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m1.compile([tx], is_train=True, use_graph=False)
    m2.compile([tx], is_train=True, use_graph=True)
    m2.set_params({k: v.numpy() for k, v in m1.get_params().items()})
    for _ in range(3):
        _, l1 = m1(tx, ty)
        _, l2 = m2(tx, ty)
    assert abs(float(l1.numpy()) - float(l2.numpy())) < 1e-4
    for k in m1.get_params():
        assert np.allclose(m1.get_params()[k].numpy(),
                           m2.get_params()[k].numpy(), atol=1e-4), k


def test_graph_step_is_compiled_once(dev, data):
    X, Y = data
    m = MLP()
    losses, _ = _train(m, dev, X, Y, 5, True)
    (ex,) = m._compiled_step.values()   # one step tag, one executor
    assert len(ex) == 1                 # five steps, one staged build
    assert m._step_stats["steps"] == 5
    assert m._step_stats["compile_s"] > 0


def test_eval_mode_uses_forward(dev, data):
    X, Y = data
    m = MLP()
    losses, _ = _train(m, dev, X, Y, 3, True)
    m.eval()
    out = m(tensor.from_numpy(X, dev))
    assert out.shape == (32, 4)


def test_checkpoint_roundtrip(tmp_path, dev, data):
    X, Y = data
    m = MLP()
    _train(m, dev, X, Y, 5, False)
    path = str(tmp_path / "ck.zip")
    m.save_states(path, aux_states={"epoch": np.int32(7)})

    m2 = MLP()
    m2.set_optimizer(opt.SGD(lr=0.2))
    m2.compile([tensor.from_numpy(X, dev)], is_train=True, use_graph=False)
    aux = m2.load_states(path)
    assert int(aux["epoch"]) == 7
    for k, v in m.get_states().items():
        assert np.allclose(v.numpy(), m2.get_states()[k].numpy()), k


def test_checkpoint_zip_layout(tmp_path, dev, data):
    import zipfile
    X, Y = data
    m = MLP()
    _train(m, dev, X, Y, 1, False)
    path = str(tmp_path / "ck.zip")
    m.save_states(path)
    with zipfile.ZipFile(path) as zf:
        assert set(zf.namelist()) == {"tensor_dict.npz", "states_attr.json"}


def test_optimizer_state_threaded_through_graph(dev, data):
    """Momentum must keep accumulating across jitted steps."""
    X, Y = data
    m = MLP()
    sgd = opt.SGD(lr=0.1, momentum=0.9)
    m.set_optimizer(sgd)
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True)
    for _ in range(3):
        m(tx, ty)
    assert float(np.asarray(sgd.step_counter)) == 3.0
    bufs = [v for st in sgd._states.values() for v in st.values()]
    assert bufs and all(float(np.abs(np.asarray(b)).max()) > 0 for b in bufs)


def test_eval_twice_and_interleave(dev):
    """Regression: jitted eval must not leak tracers into state tensors
    (second eval call used to fail with UnexpectedTracerError)."""
    import numpy as np
    from singa_tpu import layer, model, opt, tensor

    class Net(model.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(4)
            self.sce = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc(x)

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.sce(out, y)
            self.optimizer(loss)
            return out, loss

    m = Net()
    m.set_optimizer(opt.SGD(lr=0.1))
    x = tensor.Tensor(data=np.random.randn(8, 6).astype(np.float32),
                      device=dev)
    y = tensor.from_numpy(np.zeros(8, np.int32), device=dev)
    m.compile([x], is_train=True, use_graph=True)
    m(x, y)
    m.eval()
    a = m(x).numpy()
    b = m(x).numpy()          # second jitted-eval call
    np.testing.assert_array_equal(a, b)
    m.train()
    m(x, y)                   # training resumes on concrete buffers
    m.eval()
    c = m(x).numpy()
    assert not np.allclose(a, c)  # params moved


def test_sequential_serial_mode(dev):
    """compile(sequential=True) = ref RunGraph(sequential): the step runs
    eagerly op-by-op (debuggable) with identical numerics."""
    import jax as _jax
    import numpy as np
    from singa_tpu import layer, opt, tensor

    class N(model.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(4)
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc(x)

        def train_one_batch(self, x, y):
            out = self.forward(x)
            loss = self.loss_fn(out, y)
            self.optimizer(loss)
            return out, loss

    rng = np.random.RandomState(0)
    xa = rng.rand(8, 6).astype(np.float32)
    ya = rng.randint(0, 4, 8).astype(np.int32)

    def run(sequential):
        dev.rng_state = _jax.random.PRNGKey(3)
        x = tensor.from_numpy(xa, device=dev)
        y = tensor.from_numpy(ya, device=dev)
        m = N()
        m.set_optimizer(opt.SGD(lr=0.1))
        m.compile([x], is_train=True, use_graph=True,
                  sequential=sequential)
        return [float(m(x, y)[1].numpy()) for _ in range(4)]

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


def test_eval_shape_bucketing(dev):
    """Varying eval batch sizes reuse power-of-two compiled variants and
    return correctly-sized outputs."""
    import numpy as np
    from singa_tpu import layer, tensor

    class N(model.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(3)

        def forward(self, x):
            return self.fc(x)

    rng = np.random.RandomState(0)
    x16 = rng.rand(16, 5).astype(np.float32)
    m = N()
    m.compile([tensor.from_numpy(x16, device=dev)], is_train=False,
              use_graph=True, eval_buckets=True)
    m.eval()
    full = np.asarray(m(tensor.from_numpy(x16, device=dev)).numpy())
    for n in (16, 13, 7, 1):
        out = m(tensor.from_numpy(x16[:n], device=dev))
        got = np.asarray(out.numpy())
        assert got.shape == (n, 3)
        np.testing.assert_allclose(got, full[:n], rtol=1e-5, atol=1e-6)


def test_checkpoint_resume_equivalence(tmp_path, dev):
    """Full-training-state checkpoint (orbax): params + optimizer slots +
    RNG. Training resumed from step 3 in a FRESH model must produce the
    same losses as the uninterrupted run — momentum and the PRNG stream
    survive, not just weights (the zip save_states covers model states
    only, reference parity)."""
    import numpy as np
    from singa_tpu import layer, opt, tensor

    class N(model.Model):
        def __init__(self):
            super().__init__()
            self.fc1 = layer.Linear(8)
            self.relu = layer.ReLU()
            self.fc2 = layer.Linear(3)
            self.sce = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc2(self.relu(self.fc1(x)))

        def train_one_batch(self, x, y):
            loss = self.sce(self.forward(x), y)
            self.optimizer(loss)
            return loss

    rng = np.random.RandomState(0)
    X = rng.randn(16, 5).astype(np.float32)
    Y = rng.randint(0, 3, 16).astype(np.int32)

    def build():
        import jax as _jax
        dev.rng_state = _jax.random.key(7)
        m = N()
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
        tx = tensor.from_numpy(X, dev)
        ty = tensor.from_numpy(Y, dev)
        m.compile([tx], is_train=True, use_graph=True)
        return m, tx, ty

    # uninterrupted: 6 steps
    m_a, tx, ty = build()
    ref = [float(m_a(tx, ty).numpy()) for _ in range(6)]

    # interrupted: 3 steps, checkpoint, resume in a FRESH model
    m_b, tx, ty = build()
    got = [float(m_b(tx, ty).numpy()) for _ in range(3)]
    path = m_b.save_checkpoint(str(tmp_path / "ck"), step=3)

    m_c, tx, ty = build()
    _ = [m_c(tx, ty) for _ in range(1)]  # diverge first: proves restore
    m_c.load_checkpoint(path)
    got += [float(m_c(tx, ty).numpy()) for _ in range(3)]
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)


def test_checkpoint_resume_sparse_residuals(tmp_path, dev):
    """Resume must also restore the sparse strategy's error-feedback
    residuals — PER-DEVICE (each data shard keeps its own top-K
    leftovers under a replicated spec): save_checkpoint stacks every
    device's buffer and restore rebuilds them. Exact dist resume needs
    DistOpt(sparse_residuals=True) so the slots are step INPUTS from
    step 0 (review finding: they were silently dropped / collapsed to
    device 0 and resume diverged)."""
    import numpy as np
    from singa_tpu import layer, opt, tensor
    from singa_tpu.parallel import data_parallel_mesh

    class N(model.Model):
        def __init__(self):
            super().__init__()
            self.fc1 = layer.Linear(8)
            self.relu = layer.ReLU()
            self.fc2 = layer.Linear(3)
            self.sce = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.fc2(self.relu(self.fc1(x)))

        def train_one_batch(self, x, y):
            loss = self.sce(self.forward(x), y)
            self._optimizer.backward_and_sparse_update(loss, spars=0.3,
                                                       topK=True)
            return loss

    rng = np.random.RandomState(1)
    X = rng.randn(16, 5).astype(np.float32)
    Y = rng.randint(0, 3, 16).astype(np.int32)

    def build():
        import jax as _jax
        dev.rng_state = _jax.random.key(5)
        m = N()
        m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.1, momentum=0.9),
                                    mesh=data_parallel_mesh(8),
                                    sparse_residuals=True))
        tx = tensor.from_numpy(X, dev)
        ty = tensor.from_numpy(Y, dev)
        m.compile([tx], is_train=True, use_graph=True)
        return m, tx, ty

    m_a, tx, ty = build()
    ref = [float(m_a(tx, ty).numpy()) for _ in range(6)]

    m_b, tx, ty = build()
    _ = [m_b(tx, ty) for _ in range(3)]
    path = m_b.save_checkpoint(str(tmp_path / "cks"), step=3)

    m_c, tx, ty = build()   # FRESH: never trained before restore
    m_c.load_checkpoint(path)
    got = [float(m_c(tx, ty).numpy()) for _ in range(3)]
    np.testing.assert_allclose(got, ref[3:], rtol=1e-6, atol=1e-7)


def test_checkpoint_sharded_params(tmp_path, dev):
    """save_checkpoint on a model whose params carry mesh shardings
    (vocab-parallel GPT on a {data, tp} mesh): orbax writes the GLOBAL
    arrays from their shards — no host gather — and restore into a fresh
    mesh-compiled model resumes training at the checkpointed loss."""
    import numpy as np
    from singa_tpu import models, opt, tensor
    from singa_tpu.parallel import make_mesh

    rng = np.random.RandomState(3)
    V, B, S = 48, 4, 8
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)

    def build():
        import jax as _jax
        dev.rng_state = _jax.random.key(11)
        m = models.create_model(
            "gpt", vocab_size=V, max_seq=S, dim=16, num_heads=4,
            num_layers=1, tp_axis="tp", vocab_tp=True,
            vocab_pad_multiple=8)
        mesh = make_mesh({"data": 2, "tp": 4})
        m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.05), axis="data",
                                    mesh=mesh))
        tx = tensor.from_numpy(ids, dev)
        ty = tensor.from_numpy(tgt, dev)
        m.compile([tx], is_train=True, use_graph=True)
        return m, tx, ty

    m_a, tx, ty = build()
    ref = [float(m_a(tx, ty)[1].numpy()) for _ in range(4)]
    # checkpoint mid-training from the SHARDED state
    m_b, tx, ty = build()
    _ = [m_b(tx, ty) for _ in range(2)]
    path = m_b.save_checkpoint(str(tmp_path / "ck3d"), step=2)
    m_c, tx, ty = build()
    m_c.load_checkpoint(path)
    got = [float(m_c(tx, ty)[1].numpy()) for _ in range(2)]
    np.testing.assert_allclose(got, ref[2:], rtol=1e-5, atol=1e-6)


def test_eval_bucketing_auto_default(dev):
    """Default "auto" bucketing: per-sample outputs are
    detected on the first eval, and the last partial batch then runs
    WITHOUT a retrace (padded into the already-compiled bucket)."""
    import numpy as np
    from singa_tpu import layer, tensor

    class N(model.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(3)

        def forward(self, x):
            return self.fc(x)

    rng = np.random.RandomState(1)
    x16 = rng.rand(16, 5).astype(np.float32)
    m = N()
    m.compile([tensor.from_numpy(x16, device=dev)], is_train=False,
              use_graph=True)  # eval_buckets defaults to "auto"
    m.eval()
    full = np.asarray(m(tensor.from_numpy(x16, device=dev)).numpy())
    assert m._eval_per_sample is True
    traces_after_full = m._eval_trace_count
    # last partial batch: padded to 16 -> same executable, no retrace
    out = m(tensor.from_numpy(x16[:11], device=dev))
    assert out.shape == (11, 3)
    np.testing.assert_allclose(np.asarray(out.numpy()), full[:11],
                               rtol=1e-5, atol=1e-6)
    assert m._eval_trace_count == traces_after_full, \
        "partial batch retraced despite auto bucketing"


def test_eval_bucketing_auto_disables_for_reduced_outputs(dev):
    """auto must NOT bucket a forward whose output drops the batch dim —
    padding would corrupt a batch reduction; it falls back to retrace."""
    import numpy as np
    from singa_tpu import autograd, layer, tensor

    class R(model.Model):
        def __init__(self):
            super().__init__()
            self.fc = layer.Linear(3)

        def forward(self, x):
            return autograd.reduce_mean(self.fc(x), axes=[0],
                                        keepdims=False)  # (3,)

    rng = np.random.RandomState(2)
    x16 = rng.rand(16, 5).astype(np.float32)
    m = R()
    m.compile([tensor.from_numpy(x16, device=dev)], is_train=False,
              use_graph=True)
    m.eval()
    m(tensor.from_numpy(x16, device=dev))
    assert m._eval_per_sample is False
    out = m(tensor.from_numpy(x16[:10], device=dev))
    # correct mean over exactly 10 rows (no zero padding averaged in)
    ref = np.asarray(
        m(tensor.from_numpy(x16[:10], device=dev)).numpy())
    W = m.get_params()["fc.W"].numpy()
    b = m.get_params()["fc.b"].numpy()
    np.testing.assert_allclose(ref, (x16[:10] @ W + b).mean(0),
                               rtol=1e-5, atol=1e-6)
