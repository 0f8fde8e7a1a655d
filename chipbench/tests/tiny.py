"""Cells at smoke sizes for the CPU tests: the committed cells' drivers,
traffic and limits, with every size cut so that a run takes seconds.

The paper's 2FNN round (``configs/fnn2-mnist.json``) has no committed cell
(PERF.md, Open questions), but the round driver, the traffic and the
reference keep its path; ``round.fnn2-mnist.h90`` here is that cell as it
would run, at smoke size, with limits for the CPU's exact float32."""
from __future__ import annotations

import copy
import json
import os

from chipbench import run as R

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

SIZES = {
    "round.fnn2-mnist.h90": {"dims": [784, 16, 10], "n_clients": 12, "m_chains": 3,
                             "k_walk": 3, "batch_size": 8,
                             "data": {"kind": "image", "n_samples": 600, "noise": 2.0, "seed": 0}},
    "round.lstm-reddit.q8": {"vocab": 300, "embed": 8, "hidden": 16, "seq_len": 5,
                             "n_clients": 8, "m_chains": 3, "batch_size": 4,
                             "data": {"kind": "tokens", "seqs_per_client": 6,
                                      "client_vocab": 20, "seed": 0}},
    "fedstep.yi-6b-2l.1pod": {"hidden_size": 64, "intermediate_size": 128,
                              "num_attention_heads": 4, "num_key_value_heads": 2,
                              "vocab_size": 128},
}
TRAFFIC = {"fedstep.yi-6b-2l.1pod": {"batch": 2, "seq": 16}}
UNCOMMITTED = {
    "round.fnn2-mnist.h90": {
        "config": "fnn2-mnist", "driver": "round", "chips": 1,
        "traffic": {"bits": 32, "u_percent": 0, "h_percent": 90, "straggler_mode": "partial",
                    "slowdown": 5.0},
        "limits": {"loss_gap": 1e-4, "change1_gap": 1e-4, "change3_gap": 1e-4}},
}


def _load(name: str) -> dict:
    if name not in UNCOMMITTED:
        return R.load_cell(name)
    wl = UNCOMMITTED[name]
    like = R.load_cell("round.lstm-reddit.q8")          # the round cells' metrics
    with open(os.path.join(R.BENCH, "configs", f"{wl['config']}.json")) as f:
        cfg = json.load(f)
    return dict(like, name=name, wl=wl, cfg=cfg, chips=wl["chips"])


def cell(name: str) -> dict:
    """The cell, cut to smoke sizes."""
    c = copy.deepcopy(_load(name))
    c["cfg"].update(SIZES[name])
    c["wl"]["traffic"].update(TRAFFIC.get(name, {}))
    return c


def limits(name: str) -> dict:
    return _load(name)["wl"]["limits"]


def benchmark() -> dict:
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
