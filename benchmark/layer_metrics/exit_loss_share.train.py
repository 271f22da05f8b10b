"""Device time under `head`, `exit_gate` and `loop_loss` (forward, the
head's recomputation and backward) as a share of the device's busy time:
what a head, a gate and a loss at every pass cost. In the six-layer cut they
are 22 % of the model's FLOPs, in the whole 48-layer model 3 %."""

import loop_scopes

read = loop_scopes.share_reader(loop_scopes.is_exit_loss)
