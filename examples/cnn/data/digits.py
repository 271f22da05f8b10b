"""sklearn handwritten-digits loader — the one REAL dataset available in
the zero-egress sandbox (1,797 genuine 8x8 grayscale digit scans bundled
with scikit-learn). Used for recorded accuracy evidence: unlike the
synthetic mnist/cifar fallbacks, convergence here demonstrates actual
learning on actual data (BASELINE accuracy target).

Images are upsampled 8x8 -> 32x32 so the conv stacks (two stride/pool
halvings) still see a useful spatial extent. Split: 1,497 train / 300 val,
deterministic shuffle.
"""

import numpy as np


def load(upscale=4, seed=0):
    from sklearn.datasets import load_digits
    d = load_digits()
    x = d.images.astype(np.float32) / 16.0      # (1797, 8, 8) in [0,1]
    y = d.target.astype(np.int32)
    if upscale > 1:
        x = np.repeat(np.repeat(x, upscale, 1), upscale, 2)
    x = (x - 0.5) / 0.5
    x = x[:, None]                               # (N, 1, H, W)
    rng = np.random.RandomState(seed)
    idx = rng.permutation(len(x))
    x, y = x[idx], y[idx]
    n_val = 300
    return x[:-n_val], y[:-n_val], x[-n_val:], y[-n_val:]
