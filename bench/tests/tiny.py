"""Tiny versions of the cells, for driving the harness on the CPU."""

from __future__ import annotations

import copy

import run

TINY_MODEL = {
    "qwen3-8b.l16": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                     "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                     "vocab_size": 256},
}
TINY_MIX = {
    "chat": {"rate_rps": 15.0, "system_len": 32, "system_prompts": 3,
             "user_lens": [5, 12, 40], "user_median": 12,
             "out_median": 6, "out_min": 3, "out_max": 12,
             "warm_group": 2, "check_requests": 3},
}

TINY_LIMITS = {"served_gap_max": 0.002}


def spec(cell: str, chips: int = 1):
    """The cell's spec with the model, shapes and mix cut to CPU size."""
    s = copy.deepcopy(run.load_spec(cell))
    s["config"]["model"].update(TINY_MODEL[s["cell"]["config"]])
    if "serve" in s["config"]:
        s["config"]["serve"] = {"max_batch": 4, "max_len": 128}
    s["mix"].update(TINY_MIX[s["cell"]["traffic"]])
    s["cell"]["chips"] = chips
    # at these widths the fp8 control's widest gap is about 0.004-0.008
    # and the program's 0 (CPU readings): the cell's limit, set at the
    # published widths, would pass the control here
    s["own"]["limits"] = {**s["own"].get("limits", {}), **TINY_LIMITS}
    return s
