"""Roofline share of the fused quantize-dequantize kernel: the least bytes
its calls must move in the traced rounds (``flops.qdq_round_bytes``) over
the chip's HBM bandwidth, divided by the summed device time of the kernel's
events, in percent. Bound by bandwidth: the kernel does a few operations
per byte. The kernel's events are the round program's Pallas calls: the
trace names an op by its HLO text (``%closed_call.4 = f32[1575936,128]
custom-call(...), custom_call_target="tpu_custom_call", ...``), which does
not carry the kernel's name; the fused qdq kernel is the only Pallas
kernel that program calls. XLA's own custom calls (``AllocateBuffer``,
``ConcatBitcast``) name other targets."""
import re

KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')


def read(run: dict):
    tr, per_call = run["trace"], run["counts"].get("qdq_bytes_per_call")
    if not tr or not per_call or not run["slice_calls"]:
        return None
    n = max(len(tr["devices"]), 1)
    t = sum(v for k, v in tr["op_time_s"].items() if KERNEL.search(k)) / n
    if t <= 0:
        return None
    return per_call * run["slice_calls"] / run["peak"]["hbm_bytes_per_s"] / t * 100.0
