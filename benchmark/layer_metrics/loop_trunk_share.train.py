"""Device time under the passes' scopes (`ut<t>`: the shared stack and the
final norm; forward, its recomputation and backward), less the Mosaic calls
(`attn_kernel_share.train` has those), as a share of the device's busy
time. Counted at a fusion's root, like every share here."""

import loop_scopes

read = loop_scopes.share_reader(loop_scopes.is_trunk, mosaic=False)
