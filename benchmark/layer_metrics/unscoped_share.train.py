"""Device time that no program scope explains, as a share of the device's
busy time: instructions whose `op_name` holds no scope (the program's gap),
instructions with no `op_name` (the compiler made them) and operations the
step's captured text does not name. `benchmark/scopes.py` keeps the parts
apart."""

import scopes


read = scopes.share_reader(scopes.scope_seconds, "unscoped")
