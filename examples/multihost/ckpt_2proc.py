"""2-process save -> kill -> restore: bit-identical continuation.

The multi-host checkpoint story, end to end: two processes
form a 4-device global mesh, train a DP model through the Model API, call
`save_checkpoint` (orbax writes each process's shards), train 3 more steps
and record the losses. Then a FRESH pair of processes (the "kill") builds
the same model, calls `load_checkpoint` — restore targets carry the live
shardings, so each process reads back exactly its own shards — and trains
the same 3 steps. The driver asserts the two loss trajectories are
bit-identical.

Run: python examples/multihost/ckpt_2proc.py
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile

WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["SINGA_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

import numpy as np
from singa_tpu import distributed, layer, model, opt, overlap, tensor
from singa_tpu.device import get_default_device

distributed.init()
rank = distributed.process_index()
mesh = distributed.global_mesh()            # {"data": 4} over 2 procs

class Net(model.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
        self.relu = layer.ReLU()
        self.fc2 = layer.Linear(4)
        self.sce = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.fc2(self.relu(self.fc1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.sce(out, y)
        self.optimizer(loss)
        return out, loss

rng = np.random.RandomState(0)
X = rng.standard_normal((8, 10)).astype(np.float32)
Y = rng.randint(0, 4, 8).astype(np.int32)
dev = get_default_device()
tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)

m = Net()
m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.1, momentum=0.9), axis="data",
                            mesh=mesh))
m.compile([tx], is_train=True, use_graph=True)

phase = os.environ["CKPT_PHASE"]
ckpt = os.environ["CKPT_DIR"]
losses = []
if phase == "save":
    want = np.maximum(X @ m.fc1.W.numpy() + m.fc1.b.numpy(), 0) \
        @ m.fc2.W.numpy() + m.fc2.b.numpy()
    for step in range(2):
        out, l = m(tx, ty)
        if step == 0:
            # the step left its batch output sharded over both processes'
            # devices; the read gathers the global batch on each
            assert not out.data.is_fully_addressable
            np.testing.assert_allclose(out.numpy(), want, rtol=1e-5,
                                       atol=1e-5)
    path = m.save_checkpoint(ckpt, step=2)
    for _ in range(3):
        _, l = m(tx, ty)
        losses.append(float(l.numpy()))
    # the write is asynchronous: make it durable before this process exits
    overlap.wait_for_checkpoints()
else:
    m.load_checkpoint(os.path.join(ckpt, "step_2"))
    for _ in range(3):
        _, l = m(tx, ty)
        losses.append(float(l.numpy()))

with open(os.path.join(ckpt, f"losses_{phase}_{rank}.json"), "w") as f:
    json.dump(losses, f)
print(f"proc {rank} phase {phase}: losses {losses}", flush=True)
"""


def run_phase(phase, ckpt_dir, repo, port):
    env_base = {**os.environ, "SINGA_REPO": repo,
                "SINGA_COORDINATOR": f"127.0.0.1:{port}",
                "SINGA_NPROCS": "2", "JAX_PLATFORMS": "cpu",
                "CKPT_PHASE": phase, "CKPT_DIR": ckpt_dir}
    procs = []
    for rank in range(2):
        env = {**env_base, "SINGA_PROC_ID": str(rank)}
        procs.append(subprocess.Popen([sys.executable, "-c", WORKER],
                                      env=env))
    rc = [p.wait(timeout=300) for p in procs]
    assert rc == [0, 0], rc


def main():
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", ".."))
    ckpt_dir = tempfile.mkdtemp(prefix="singa_ckpt2p_")
    try:
        run_phase("save", ckpt_dir, repo, 29517)
        # the "kill": phase-one processes have exited; fresh ones restore
        run_phase("restore", ckpt_dir, repo, 29518)
        with open(os.path.join(ckpt_dir, "losses_save_0.json")) as f:
            want = json.load(f)
        for phase, rank in (("save", 1), ("restore", 0), ("restore", 1)):
            with open(os.path.join(
                    ckpt_dir, f"losses_{phase}_{rank}.json")) as f:
                got = json.load(f)
            assert got == want, (phase, rank, got, want)
        print(f"2-process save->kill->restore: bit-identical continuation "
              f"{want}")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
