"""Device time of a looped model's step by its own scopes, for the readers
of `loop_trunk_share.train`, `exit_loss_share.train` and
`recompute_share.train`.

The program (models/looplm.py) puts pass t's stack and final norm under
`ut<t>`, each pass's head under `head`, gate under `exit_gate`, losses under
`loop_loss`; the backward of each behind a leading `bwd`; and the second
forward of a region rebuilt in the backward pass (autograd.Region) behind a
leading `recompute`: `recompute/ut3/TransformerBlock_2/fc1/...`. scopes.py
parses the names; this file only picks.
"""

import kernels
import scopes

RECOMPUTE = "recompute"
EXIT_LOSS = ("head", "exit_gate", "loop_loss")


def _own(path):
    """(recomputed?, the path less a leading `recompute`)."""
    again = bool(path) and path[0] == RECOMPUTE
    return again, (path[1:] if again else path)


def is_trunk(path):
    path = _own(path)[1]
    return bool(path) and path[0].startswith("ut") and path[0][2:].isdigit()


def is_exit_loss(path):
    path = _own(path)[1]
    return bool(path) and path[0] in EXIT_LOSS


def is_recomputed(path):
    return _own(path)[0]


def seconds(trace, hlo_dir, pick, mosaic=True):
    """Seconds of the traced stretch in the step's instructions whose scope
    path `pick` takes, the Mosaic calls among them left out unless
    `mosaic`. Raises ValueError where the step's text has no pass scope at
    all (another model's program, or one from before the scopes)."""
    table = scopes.instructions(hlo_dir)
    if not any(i["path"] and is_trunk(i["path"]) for i in table.values()):
        raise ValueError("the step's text holds no `ut<t>` scope")
    skip = () if mosaic else kernels.mosaic_calls(hlo_dir)
    return sum(t for n, t in trace["self_s"].items()
               if n in table and n not in skip and table[n]["path"]
               and pick(table[n]["path"]))


def share_reader(pick, mosaic=True):
    @scopes.reader
    def read(record, trace):
        return scopes.share(
            trace, seconds(trace, record["hlo_dir"], pick, mosaic))
    return read
