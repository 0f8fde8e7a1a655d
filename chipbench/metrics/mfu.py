"""Model FLOPs per second over the chips' bf16 peak, in percent: the
reader of every ``mfu.<kind>`` metric. The driver counts a call's model
FLOPs from the model's shapes (``flops.round_flops`` for a protocol round:
forward and backward of the K x M batches and the loss evaluation;
``flops.decoder_train_flops_per_token`` times the tokens of a pod step)."""


def read(run: dict):
    flops = run["counts"].get("model_flops_per_call")
    if not flops or not run["calls"]:
        return None
    rate = flops * len(run["calls"]) / run["window_s"]
    return rate / (run["chips"] * run["peak"]["bf16_flops_per_s"]) * 100.0
