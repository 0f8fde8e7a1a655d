"""Roofline share of the held experts' grouped matmul: the least FLOPs its
calls must do in the traced steps (``mla_moe_flops.experts_least_flops``:
forward and backward of the three SwiGLU matmuls over the rows the held
experts expect) over the chips' bf16 peak, divided by the summed device
time of its ops, in percent. Bound by compute at these widths. The ops are
the Pallas megablox kernels of the ``moe/experts`` scope, which the trace
names by their HLO text: ``%gmm.<n> = f32[...] custom-call(...),
custom_call_target="tpu_custom_call"`` (the forward products and the rows'
gradients) and ``%tgmm.<n>`` (the weights' gradients). A run whose trace
holds none of them reads nothing."""
import re

KERNEL = re.compile(r'^%t?gmm(\.\d+)? = .*custom_call_target="tpu_custom_call"')


def read(run: dict):
    tr, per_call = run["trace"], run["counts"].get("experts_flops_per_call")
    if not tr or not per_call or not run["slice_calls"]:
        return None
    n = max(len(tr["devices"]), 1)
    t = sum(v for k, v in tr["op_time_s"].items() if KERNEL.match(k)) / n
    if t <= 0:
        return None
    flops = per_call * run["slice_calls"] / run["chips"]
    return flops / run["peak"]["bf16_flops_per_s"] / t * 100.0
