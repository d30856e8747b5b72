"""Names the program gives its own work, on the device and on the host.

**Device half** (``jax.named_scope``): the scopes the epoch program wears. A
scope is metadata on the operations traced under it (the HLO ``op_name``,
shown by xprof as an op's ``tf_op``): no switch, no cost, always there, like
a function's name.

**Host half** (telemetry/tracer.py ``SpanTracer.span``): the spans the fit loop
opens around its own host work. The tracer writes each one, under
``HOST_PREFIX``, into the host plane of whatever ``jax.profiler`` session is
running, on the clock the device's operations are on, whatever
``cfg.telemetry`` says; with telemetry on the same names (without the prefix)
are in ``trace.jsonl``. Every one is opened with ``epoch=``.

Constants, so that a rename is one edit here and fails tests/test_scopes.py
and benchmarks/tests/ instead of reading 0.0 or nothing on the chip.
"""

# -- device half -------------------------------------------------------------

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
# a ``conv`` layer's token mixer (lfm2_moe), its two projections included
SHORT_CONV = "model/short_conv"
# a ``linear_attention`` layer's token mixer (qwen3_next: Gated DeltaNet),
# its projections, convolution, gates and norm included
DELTA_RULE = "model/delta_rule"
# nests in it: the chunked recurrence alone (models/afmoe.py chunked_delta_rule)
DELTA_CHUNKS = "model/delta_chunks"

# -- host half ---------------------------------------------------------------

HOST_PREFIX = "dinunet/"  # a profiler event is named HOST_PREFIX + span name
# trainer/prefetch.py EpochPlanPrefetcher.get: the loop blocked on the builder
PLAN_WAIT = "plan-wait"
# trainer/loop.py _build_epoch_payload, on whichever thread builds: index
# plan, fault windows, put_epoch_plan
PLAN_BUILD = "plan-build"
# run_epoch, first line to the dispatch: plan unpack, _ensure_inventory,
# transfer bytes, slice liveness (host pipeline: plan_epoch ... _put_batch)
EPOCH_INPUTS = "epoch-inputs"
# the epoch_fn(...) call alone: trace-cache lookup, argument handling, enqueue
EPOCH_DISPATCH = "epoch-dispatch"
# np.asarray(losses) alone: the wait for the device, then the copy to the host
LOSS_FETCH = "loss-fetch"
EPOCH_ACCOUNT = "epoch-account"  # _account_epoch: the privacy ledger, its gauge
# _ensure_inventory's miss: stack, cast, put_site_inventory
INVENTORY_UPLOAD = "inventory-upload"
#: every host span of the fit loop; tests/test_scopes.py holds the list to the
#: constants above and each name a benchmark metric reads to this list
HOST_SPANS = (PLAN_WAIT, PLAN_BUILD, EPOCH_INPUTS, EPOCH_DISPATCH, LOSS_FETCH,
              EPOCH_ACCOUNT, INVENTORY_UPLOAD)
