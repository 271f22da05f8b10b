"""Device time counted under the scope `opt` (the optimizer's update of each
parameter and its step counter) as a share of the device's busy time. A
floor on the optimizer's cost, not the cost: a fusion counts under its
root's scope, and an update fused into the matmul that makes its gradient
counts under the layer (`opt_fused_share.train` reads how much that is).
Unfusing the update moves this up and that down; judge by their sum."""

import scopes


read = scopes.share_reader(scopes.scope_seconds, "opt")
