"""Names of the device scopes (``jax.named_scope``) the epoch program wears.

A scope is metadata on the operations traced under it (the HLO ``op_name``,
shown by xprof as an op's ``tf_op``): no switch, no cost, always there, like
a function's name. Constants, so that a rename is one edit here and fails
tests/test_scopes.py and benchmarks/tests/test_scope_metrics.py instead of
reading 0.0 on the chip.
"""

GATHER = "data/gather"  # trainer/steps.py _gather_batch
MODEL = "model/fwd_bwd"  # trainer/steps.py grad_fn: forward, loss, backward
ENGINE = "engine/aggregate"  # trainer/steps.py engine_aggregate
POWERITER = "poweriter"  # engines/lowrank.py, nests: engine/aggregate/poweriter
OPTIMIZER = "optimizer/update"  # trainer/steps.py: optimizer.update + apply
# models/afmoe.py, inside model/fwd_bwd
ATTENTION_WINDOW = "model/attention_window"  # a sliding layer's attention
ATTENTION_FULL = "model/attention_full"  # a full layer's attention
MOE_ROUTE = "model/moe_route"  # router scores, top-k, weights
MOE_EXPERTS = "model/moe_experts"  # sort, grouped products, combine
MOE_SHARED = "model/moe_shared"  # the shared expert
LM_HEAD = "model/lm_head"  # final norm, head, softmax, in sequence blocks
ATTENTION_MLA = "model/attention_mla"  # a latent-attention layer's attention
# nests in it: the low-rank down- and up-projections and the latent norms
MLA_LATENT = "model/mla_latent"
MTP = "model/mtp"  # the second prediction depth: projection, block, head
