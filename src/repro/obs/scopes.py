"""One name per layer of the two chip programs, and of the round engine's
host spans.

The device scopes are ``jax.named_scope`` names: metadata only, they change
no op, fusion or number. XLA keeps them in every instruction's
``metadata={op_name="jit(<fn>)/<scope>/..."}`` of the compiled program
(``jit(f).lower(...).compile().as_text()``), under the instruction names a
profiler trace shows, so a trace's device time can be summed per scope by
joining the two. A backward pass shows as ``transpose(jvp(<scope>))``.

The host spans are ``Recorder.span`` names; on a wall clock each is also a
``jax.profiler.TraceAnnotation`` of the same name, on the profiler's clock
beside the device ops.
"""
from __future__ import annotations

__all__ = ["ROUND_SCOPES", "FEDSTEP_SCOPES", "ENGINE_SPANS"]

# flat round program (core/dfedrw.DFedRW._build_round_fn_flat)
WALK_SGD = "walk/sgd"              # batch gather, vmapped grad, SGD step, straggler mask
WALK_HOP_QDQ = "walk/hop_qdq"      # the hop's payload quantize-dequantize (Eq. 13)
SCATTER = "scatter"                # winner election and the w^{t,last} row scatter
AGGREGATE_QDQ = "aggregate/qdq"    # Eq. 14 messages: base rows, winner diffs, qdq
AGGREGATE_MIX = "aggregate/mix"    # weights, weighted sum, aggregator-row set
LOSS = "loss"                      # monitoring loss and gamma_hat
ROUND_SCOPES = (WALK_SGD, WALK_HOP_QDQ, SCATTER, AGGREGATE_QDQ, AGGREGATE_MIX, LOSS)

# pod fed step (dist/steps.make_fed_train_step)
FORWARD = "forward"                # the loss; its backward is transpose(jvp(forward))
OPTIMIZER = "optimizer"            # learning rate and momentum SGD
GOSSIP = "gossip"                  # the gossip mix over the pod axis
# inside forward (models/layers.py), where the model has them
MLA = "mla"                        # latent attention: projections, rope, scores, values
MOE_ROUTE = "moe/route"            # router logits, softmax, top-k, balance loss
MOE_DISPATCH = "moe/dispatch"      # sort of the assignments, group sizes, row gather
MOE_EXPERTS = "moe/experts"        # the held experts' grouped matmuls
MOE_COMBINE = "moe/combine"        # rows back to their tokens, times the gates
MOE_SHARED = "moe/shared"          # the shared experts' SwiGLU
FEDSTEP_SCOPES = (FORWARD, OPTIMIZER, GOSSIP, MLA, MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS,
                  MOE_COMBINE, MOE_SHARED)

# round engine host spans (core/dfedrw.DFedRW.run_round / execute_round)
ENGINE_PLAN = "engine/plan"                # walk and aggregation planning
ENGINE_EXECUTE = "engine/execute_round"    # the three below, in this order
ENGINE_DISPATCH = "engine/dispatch"        # argument upload and the program's enqueue
ENGINE_ACCOUNT = "engine/account"          # retrace check, Eq. 18 accounting, new state
ENGINE_WAIT = "engine/wait"                # reading the loss back: waits for the device
ENGINE_SPANS = (ENGINE_PLAN, ENGINE_EXECUTE, ENGINE_DISPATCH, ENGINE_ACCOUNT, ENGINE_WAIT)
