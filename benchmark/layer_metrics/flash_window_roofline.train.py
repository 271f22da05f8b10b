"""The sliding layers' flash passes against the pairs inside their window
(moe_scopes.flash_roofline_reader): kernels whose name ends in `_win`."""

import moe_scopes

read = moe_scopes.flash_roofline_reader(True)
