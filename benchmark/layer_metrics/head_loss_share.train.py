"""Device time counted under the scopes of the output head and the loss
(`head`, `sce` with its reshapes; forward and backward) as a share of the
device's busy time. Counted at a fusion's root, like every share here."""

import scopes


read = scopes.share_reader(scopes.scope_seconds, "head_loss")
