"""The full layers' flash passes against the causal pairs
(moe_scopes.flash_roofline_reader): kernels without the window's suffix."""

import moe_scopes

read = moe_scopes.flash_roofline_reader(False)
