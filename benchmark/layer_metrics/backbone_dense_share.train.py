"""Device time counted under the scopes of the model's trunk (`tok_embed`,
`pos_embed`, `TransformerBlock_<i>`, `ln_f`; forward and backward) less the
Mosaic calls in it (the flash kernels, which `attn_kernel_share.train`
counts), as a share of the device's busy time: the dense matmuls, norms and
residual adds, and, a fusion counting under its root's scope, the
optimizer's updates that XLA fused into the matmuls that make their
gradients (`opt_fused_share.train` reads how much time those fusions take).
Unfusing an update moves this down with no work saved in the trunk."""

import kernels
import scopes


@scopes.reader
def read(record, trace):
    hlo = record["hlo_dir"]
    in_trunk = lambda key, op: key == "step" and scopes.group_of(
        scopes.parse_op_name(op)[1]) == "backbone"
    return scopes.share(
        trace, scopes.scope_seconds(trace, hlo, "backbone")[0]
        - kernels.attention_seconds(trace, hlo, in_trunk)[0])
