"""Device time of the fusions that hold instructions of scope `opt` in their
body and are counted under another scope, their root's (XLA fuses Adam's
update of a weight into the matmul that makes its gradient), as a share of
the device's busy time. Time inside a fusion cannot be split, so the
optimizer's cost lies between `opt_update_share.train` and that plus this."""

import scopes


read = scopes.share_reader(scopes.fused_elsewhere_seconds, "opt")
