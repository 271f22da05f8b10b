"""Device time of the second forward of the regions that the backward pass
rebuilds (scope `recompute/...`, the flash kernel's forward calls among
them) as a share of the device's busy time: what fitting the loop's
activations into the chip costs."""

import loop_scopes

read = loop_scopes.share_reader(loop_scopes.is_recomputed)
